"""Closed-loop long-cache decode of DeepSeek-V2 (latent attention, held
experts) through ``PagedServer``: the loop and the requests of
:mod:`bench.drivers.serve_closed`, with this architecture's program
config, weights and reference.

Traffic parameters as in ``serve_closed``.  Every slot is filled, and
the decode step compiled, in set-up; outputs are long enough that no
request finishes, and the pool large enough that none is preempted, in
the window: the run stops with an error where either happens.

End to end: ``output_tokens_per_s``, the tokens generated in the window
over the window.

The comparison: ``check_requests`` of the requests served (the one with
the most served tokens among them, the rest drawn by the seed; every
one where ``check_requests`` reaches the number served), each prompt
with every token served, through the plain float32 forward
(``bench.reference.deepseek_v2``); the number is the widest gap by which
a served token's reference logit lies below the reference's best at its
position (greedy decoding).  The control reads the same gap for the
token a float8 copy of the weights puts first.  The cell's limit, 0.3,
is over twice the widest gap the program gave on one TPU v5e (0.135,
where a route flips between bfloat16 and float32 and a token uses
another held expert) and under a third of the control's narrowest
(1.05).
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.serve_closed import Requests, _Loop
from bench.harness import Check, Result
from bench.reference import deepseek_v2 as ds
from bench.reference import prng_key


def program_config(config: dict, tr: dict):
    """The program's config for this configuration file: every size and
    constant the file states, the held experts, the served dtype, the
    decode kernel."""
    from repro.configs import get_config
    dm = ds.dims(config)
    dtype = config["torch_dtype"]
    yarn = dm["yarn"]
    return get_config(config["program_arch"]).replace(
        n_layers=dm["layers"], d_model=dm["d_model"], n_heads=dm["heads"],
        n_kv_heads=dm["heads"], q_lora_rank=0, kv_lora_rank=dm["kv_lora"],
        qk_nope_dim=dm["nope"], qk_rope_dim=dm["rope"],
        v_head_dim=dm["v_dim"], d_ff=dm["d_ff"], d_ff_expert=dm["expert_ff"],
        first_dense=dm["dense_layers"], n_experts=dm["experts"],
        experts_first=dm["held_first"], experts_held=dm["held"],
        top_k=dm["top_k"], n_shared_experts=dm["shared"],
        topk_method=config["topk_method"], norm_topk_prob=dm["norm_topk"],
        routed_scaling_factor=dm["routed_scale"],
        vocab_size=int(config["vocab_size"]), norm_eps=dm["eps"],
        rope_theta=dm["theta"], yarn_factor=yarn[0],
        yarn_original_max_pos=yarn[1], yarn_beta_fast=yarn[2],
        yarn_beta_slow=yarn[3], yarn_mscale=yarn[4],
        yarn_mscale_all_dim=yarn[5], dtype=dtype, param_dtype=dtype,
        attn_decode_kernel=tr["decode_kernel"])


def _program_layer(w):
    mixer = {k: w[k] for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")}
    out = {"norm1": {"scale": w["norm1"]}, "mixer": mixer,
           "norm2": {"scale": w["norm2"]}}
    if "router" in w:
        out["ffn"] = {"router": w["router"].astype(jnp.float32),
                      "wg": w["ex_wg"], "wi": w["ex_wi"], "wo": w["ex_wo"],
                      "shared": {"wg": w["sh_wg"], "wi": w["sh_wi"],
                                 "wo": w["sh_wo"]}}
    else:
        out["ffn"] = {"wg": w["wg"], "wi": w["wi"], "wo": w["wo_mlp"]}
    return out


def program_params(key, dm: dict):
    """The benchmark's weights in the program's parameter tree, made on
    the device in one jitted call (the expert layers stacked for the
    program's scan)."""
    first = dm["dense_layers"]

    @jax.jit
    def make(key):
        o = ds.outer_weights(key, dm)
        p = {"embed": {"table": o["embed"]},
             "final_norm": {"scale": o["final_norm"]},
             "lm_head": {"w": o["head"]}}
        for i in range(first):
            p[f"prefix_{i}"] = _program_layer(
                ds.dense_layer_weights(key, i, dm))
        p["blocks"] = {"slot_0": _program_layer(jax.vmap(
            lambda i: ds.moe_layer_weights(key, i, dm))(
                jnp.arange(first, dm["layers"])))}
        return p
    return make(key)


def run(h) -> Result:
    from repro.launch.serve import PagedServeConfig, PagedServer

    tr, config = h.traffic, h.config
    dm = ds.dims(config)
    clients = int(tr["clients"])
    mcfg = program_config(config, tr)
    specs = Requests(tr, int(config["vocab_size"]), h.seed)
    key = prng_key(h.seed)
    params = program_params(key, dm)
    server = PagedServer(mcfg, params, PagedServeConfig(
        max_len=int(tr["max_len"]), num_slots=clients,
        page_size=int(tr["page_size"]), num_pages=int(tr["num_pages"])))
    loop = _Loop(h, server, specs, clients)
    for c in range(clients):
        loop.submit(c)
    loop.admit()                     # fills every slot: every prefill
    loop.step()                      # compiles the decode step

    secs = h.window_seconds()
    before = len(server.moe_routes_held)
    with h.window():
        loop.in_window = True
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs or len(loop.steps) < 2:
            loop.step()
        seconds = time.perf_counter() - t0
        loop.in_window = False
    h.read_memory()
    if any(isinstance(e, dict) and e.get("kind") == "preempt"
           for e in server.events):
        raise RuntimeError("a request was preempted: the pool no longer "
                           "holds the cell's traffic")
    if server.done:
        raise RuntimeError(f"{len(server.done)} requests finished: the "
                           f"cell's outputs no longer outlast the run")

    served = {r.rid: (r.prompt, list(r.out)) for r in server.slots
              if r is not None}
    work = {"mla_contexts": loop.steps, "mla_prefill_tokens": loop.prefills,
            "moe_routes_held": server.moe_routes_held[before:],
            "mla_dims": dm}
    del server, params, loop.server
    gc.collect()

    gap = _widest_gap(key, h.seed, config, served, tr, control=h.control)
    return Result(
        metrics={"output_tokens_per_s": (loop.tokens / seconds,
                                         "tokens/s")},
        attempted=len(loop.served), failed=0,
        checks=[Check("served_logit_gap", gap, float(tr["limit"]))],
        work=work)


def _widest_gap(key, seed, config, served, tr, *, control):
    """Widest gap, over the requests compared, between the reference's
    best logit and the logit of the served (or, under ``control``, the
    float8 copy's first) token at each position."""
    rng = np.random.default_rng([seed % 2 ** 64, 3])
    rids = sorted(served)
    longest = max(rids, key=lambda r: len(served[r][1]))
    rest = [r for r in rids if r != longest]
    k = min(len(rest), int(tr["check_requests"]) - 1)
    pick = [longest] + ([rest[i] for i in sorted(
        rng.choice(len(rest), k, replace=False))] if k else [])
    seqs = [np.concatenate([served[r][0], np.asarray(served[r][1],
                                                     np.int32)])
            for r in pick]
    block = min(ds.Q_BLOCK, max(len(s) for s in seqs))
    length = -(-max(len(s) for s in seqs) // block) * block
    toks = np.zeros((len(pick), length), np.int32)
    mask = np.zeros(toks.shape, bool)    # next token was served
    for i, (rid, seq) in enumerate(zip(pick, seqs)):
        toks[i, :len(seq)] = seq
        mask[i, len(served[rid][0]) - 1:len(seq) - 1] = True
    nxt = np.roll(toks, -1, axis=1)
    hid = ds.hidden(key, toks, config)
    w = ds.head(key, config)
    if control:
        hid_c = ds.hidden(key, toks, config, control=True)
        w_c = ds.head(key, config, control=True)
    gaps = []

    @jax.jit
    def chunk_gap(hh, ww, nx):
        logits = hh @ ww
        chosen = jnp.take_along_axis(logits, nx[..., None], -1)[..., 0]
        return jnp.max(logits, -1) - chosen

    @jax.jit
    def chunk_first(hh, ww):
        return jnp.argmax(hh @ ww, axis=-1).astype(jnp.int32)

    with jax.default_matmul_precision("highest"):
        for a in range(0, length, block):
            nx = jnp.asarray(nxt[:, a:a + block])
            if control:
                nx = chunk_first(hid_c[:, a:a + block], w_c)
            g = np.asarray(chunk_gap(hid[:, a:a + block], w, nx))
            gaps.append(g[mask[:, a:a + block]])
    gaps = np.concatenate(gaps)
    print(f"compared {gaps.size} served tokens of {len(pick)} requests; "
          f"{int(np.sum(gaps == 0))} are the reference's first",
          file=sys.stderr)
    return float(np.max(gaps))
