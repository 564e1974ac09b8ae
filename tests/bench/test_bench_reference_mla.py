"""The DeepSeek-V2 plain reference against the program at smoke size on
the CPU, on the benchmark's seeded weights: the program's paged path
(batch-1 prefill, the scatter into latent pages, then decode steps at
per-slot positions through the latent kernel or its XLA rung) gives the
reference's full-forward logits at every position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.drivers import serve_closed_mla as drv
from bench.reference import deepseek_v2 as ds
from bench.reference import prng_key

#: the smoke() widths of ``repro.configs.deepseek_v2_lite_16b``; the
#: routing (64 experts, top 6, 8 held) as published
SMOKE = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "vocab_size": 512,
         "torch_dtype": "float32"}


def _config(**kw):
    return {**harness.load_json("configs", "deepseek-v2-lite-16b.json"),
            **SMOKE, **kw}


@pytest.mark.parametrize("kernel,held_first", [("blockspace", 0),
                                               ("xla", 8)])
def test_paged_prefill_then_decode_matches_the_reference(kernel, held_first):
    from repro.models import model as M
    config = _config(experts_held_first=held_first)
    dm = ds.dims(config)
    key = prng_key(2 ** 33 + 7)
    cfg = drv.program_config(config, {"decode_kernel": kernel})
    params = drv.program_params(key, dm)
    prompt, steps, ps = 11, 9, 4
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5),
                                         (1, prompt + steps), 0, 512))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ds.hidden(key, toks, config) @ ds.head(key, config))

    logits, caches = M.prefill(params, jnp.asarray(toks[:, :prompt]), cfg)
    np.testing.assert_allclose(logits[0, 0], want[0, prompt - 1], rtol=2e-4,
                               atol=2e-4)
    # slot 1 holds the request in shuffled pages; slot 0 is idle
    pages = jnp.asarray([9, 4, 12, 2, 7], jnp.int32)
    pools = M.scatter_prefill_pages(M.init_paged_cache(cfg, 16, ps), caches,
                                    pages[:3], cfg)
    table = jnp.zeros((2, 6), jnp.int32).at[1, :5].set(pages)
    for t in range(prompt, prompt + steps - 1):
        inp = jnp.zeros((2, 1), jnp.int32).at[1, 0].set(int(toks[0, t]))
        logits, pools, loads = M.decode_step_paged(
            params, inp, pools, table, jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]), cfg)
        np.testing.assert_allclose(logits[1, 0], want[0, t], rtol=2e-4,
                                   atol=2e-4)
        assert loads.shape == (dm["layers"] - dm["dense_layers"], dm["held"])


def test_reference_is_independent_of_the_held_share_outside_it():
    """The reference's expert weights come from each expert's global id:
    the share's experts are the same tensors whichever share is held."""
    dm8 = ds.dims(_config(experts_held_first=8))
    dm0 = ds.dims(_config(experts_held_first=0, n_routed_experts=16))
    key = prng_key(3)
    a = ds.moe_layer_weights(key, 1, dm8)["ex_wg"]
    b = ds.moe_layer_weights(key, 1, dm0)["ex_wg"][8:]
    np.testing.assert_array_equal(a, b)
