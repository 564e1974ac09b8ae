"""The DeepSeek-V2-Lite long-cache cell rehearsed on the CPU at a tiny
size: a sound run is correct, a broken latent append and the float8
control are not.  Every served request is compared (``check_requests``
equals the clients), so a fault cannot hide in an unsampled request."""
import time

import jax
import pytest

from bench import harness
from tiny_cells import PEAKS

CELL = "dsv2lite.longcache-decode-c16"
#: the widths cut to a CPU size; the routing (64 experts, top 6, 8 held)
#: is the published one
DSV2 = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "vocab_size": 512}
SERVE = {"clients": 4, "prompt_lens": [8, 16], "output_lens": [48, 48],
         "max_len": 64, "page_size": 4, "num_pages": 1 + 4 * 16,
         "check_requests": 4, "trace_seconds": 0.05}


def run(*, seed=2 ** 31 + 11, seconds=0.05, trace=0, control=0, config=None,
        **traffic):
    w, c = harness.load_cell(CELL)
    w = dict(w, traffic={**w["traffic"], **SERVE, **traffic})
    c = {**c, **DSV2, **(config or {})}
    return harness.run_cell(w, c, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), peaks=PEAKS,
                            control=control)


def test_sound_run_is_correct():
    line = run()
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert set(m) == {"output_tokens_per_s", "setup_s"}
    assert m["output_tokens_per_s"]["value"] > 0
    assert line["attempted"] == 4


def test_traced_run_reports_the_latent_metrics():
    line = run(trace=1)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert {"device_idle.serve", "mfu.serve-mla", "guard_ms_per_step.serve",
            "decode_call_ms_per_step.serve",
            "bookkeeping_ms_per_step.serve"} <= set(m)
    assert not {"mfu.serve", "paged_decode_roofline",
                "device_idle.gasket"} & set(m)
    assert 0 < m["mfu.serve-mla"]["value"] < 100


def test_control_is_not_correct():
    """The float8 control against the cell's own limit, at a width where
    its rounding shows through four layers, over about 30 tokens a
    request."""
    wider = {"hidden_size": 128, "intermediate_size": 256,
             "moe_intermediate_size": 64, "num_hidden_layers": 4}
    kw = dict(config=wider, seed=2 ** 31 + 11, seconds=0.3,
              output_lens=[96, 96], max_len=112, num_pages=1 + 4 * 28)
    assert run(**kw)["correct"]
    assert not run(control=1, **kw)["correct"]


@pytest.mark.parametrize("fault", ["dropped", "shifted"])
def test_a_broken_latent_append_is_not_correct(monkeypatch, fault):
    """The decode step's latent row written nowhere, or one position
    late: every served request is compared, and the run is not
    correct."""
    from repro.core import paged
    orig = paged.append_latent

    def broken(pool, page_table, pos, rows, active=None):
        if fault == "dropped":
            return pool
        return orig(pool, page_table, pos + 1, rows, active)
    monkeypatch.setattr(paged, "append_latent", broken)
    assert not run()["correct"]


def test_a_finished_request_stops_the_run():
    """Outputs shorter than the run: the driver refuses to report."""
    with pytest.raises(RuntimeError, match="finished"):
        run(output_lens=[2, 2])
