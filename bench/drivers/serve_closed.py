"""Closed-loop chat traffic through ``PagedServer``: ``clients`` clients,
each sending its next request as soon as the last one finished, no think
time.

Traffic parameters: ``clients`` (one decode slot each), ``prompt_lens``
(cycled over each round of ``clients`` requests), ``output_lens``
([lo, hi]: each round's outputs evenly spaced over it), ``page_size``,
``num_pages``, ``max_len``, ``decode_kernel``, ``check_requests``,
``trace_seconds``, ``limit``.  Every seed gets the same sizes; the seed shuffles
them within each round and draws the prompt tokens.  All slots are
filled, and the decode step compiled, in set-up.

End to end: ``output_tokens_per_s``, the tokens generated in the window
over the window.

The comparison: a sample of the requests served (the one with the most
served tokens among them), each prompt with every token served, through
the plain float32 forward (``bench.reference.phi3``); the number is the
widest gap by which a served token's reference logit lies below the
reference's best at its position (greedy decoding).  The control reads
the same gap for the token a float8 copy of the weights puts first.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Result
from bench.reference import phi3, prng_key


def program_config(config: dict, tr: dict):
    """The program's config for this configuration file: every size and
    constant the file states, the served dtype, the decode kernel."""
    from repro.configs import get_config
    dm = phi3.dims(config)
    dtype = config["torch_dtype"]
    return get_config(config["program_arch"]).replace(
        n_layers=dm["layers"], d_model=dm["d_model"], n_heads=dm["heads"],
        n_kv_heads=dm["kv_heads"], d_ff=dm["d_ff"],
        vocab_size=int(config["vocab_size"]),
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        dtype=dtype, param_dtype=dtype,
        attn_decode_kernel=tr["decode_kernel"])


def program_params(key, dm: dict):
    """The benchmark's weights in the program's parameter tree, made on
    the device in one jitted call (layers stacked for the program's
    scan)."""
    @jax.jit
    def make(key):
        o = phi3.outer_weights(key, dm)
        w = jax.vmap(lambda i: phi3.layer_weights(key, i, dm))(
            jnp.arange(dm["layers"]))
        return {"embed": {"table": o["embed"]},
                "blocks": {"slot_0": {
                    "norm1": {"scale": w["norm1"]},
                    "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                    "norm2": {"scale": w["norm2"]},
                    "ffn": {"wi": w["wi"], "wg": w["wg"],
                            "wo": w["wo_mlp"]}}},
                "final_norm": {"scale": o["final_norm"]},
                "lm_head": {"w": o["head"]}}
    return make(key)


class Requests:
    """The cell's requests, made from the seed round by round as the loop
    asks for them: request ``k * clients + c`` is client c's k-th, a
    (prompt tokens, max_new) pair.  Each round has the same sizes."""

    def __init__(self, tr: dict, vocab: int, seed: int):
        self._rng = np.random.default_rng([seed % 2 ** 64, 2])
        self._clients, self._vocab = int(tr["clients"]), vocab
        lens = [int(x) for x in tr["prompt_lens"]]
        lo, hi = (int(x) for x in tr["output_lens"])
        c = self._clients
        self._prompts = [lens[i % len(lens)] for i in range(c)]
        self._outputs = [lo + round((hi - lo) * i / max(1, c - 1))
                         for i in range(c)]
        self._made = []

    def __getitem__(self, rid: int):
        while len(self._made) <= rid:
            p, o = list(self._prompts), list(self._outputs)
            self._rng.shuffle(p)
            self._rng.shuffle(o)
            self._made += [(self._rng.integers(0, self._vocab, p[c]).astype(
                np.int32), o[c]) for c in range(self._clients)]
        return self._made[rid]


class _Loop:
    """Drives the server as the closed loop and counts the tokens served
    in the window."""

    def __init__(self, h, server, specs, clients):
        self.h, self.server, self.specs = h, server, specs
        self.clients = clients
        self.sent = [0] * clients
        self.count, self.finished = {}, set()
        self.in_window = False
        self.tokens = 0
        self.served = set()          # rids that got a token in the window
        self.steps = []              # per window step: live contexts
        self.prefills = []           # per window admission: prompt length

    def submit(self, c):
        rid = self.sent[c] * self.clients + c
        self.sent[c] += 1
        prompt, max_new = self.specs[rid]
        self.server.submit(rid, prompt, max_new)

    def _sweep(self):
        live = {r.rid: r.out for r in self.server.slots if r is not None}
        done = [rid for rid in self.server.done if rid not in self.finished]
        for rid in done:
            live[rid] = self.server.done[rid]
            self.finished.add(rid)
        for rid, out in live.items():
            new = len(out) - self.count.get(rid, 0)
            if new <= 0:
                continue
            if self.in_window:
                self.tokens += new
                self.served.add(rid)
            self.count[rid] = len(out)
        return done

    def admit(self):
        while True:
            pending = self.server.pending[0] if self.server.pending else None
            with self.h.span("PagedServer._admit_one"):
                ok = self.server._admit_one()
            if not ok:
                break
            if self.in_window:
                self.prefills.append(len(pending.prompt) + len(pending.out))
            self._sweep()

    def step(self):
        if self.in_window:
            self.steps.append([r.next_pos + 1 for r in self.server.slots
                               if r is not None])
        with self.h.span("PagedServer.step"):
            self.server.step()
        for rid in self._sweep():
            self.submit(rid % self.clients)
        self.admit()


def run(h) -> Result:
    from repro.launch.serve import PagedServeConfig, PagedServer

    tr, config = h.traffic, h.config
    dm = phi3.dims(config)
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
    loop.admit()                     # fills every slot: both prefills
    loop.step()                      # compiles the decode step

    secs = h.window_seconds()
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

    served = {}
    for r in server.slots:
        if r is not None:
            served[r.rid] = (r.prompt, list(r.out))
    for rid, out in server.done.items():
        served[rid] = (specs[rid][0], list(out))
    del server, params, loop.server
    gc.collect()

    gap = _widest_gap(key, h.seed, config, served, tr, control=h.control)
    work = {"decode_contexts": loop.steps, "prefill_tokens": loop.prefills,
            "dims": dm}
    return Result(
        metrics={"output_tokens_per_s": (loop.tokens / seconds,
                                         "tokens/s")},
        attempted=len(loop.served), failed=0,
        checks=[Check("served_logit_gap", gap, float(tr["limit"]))],
        work=work)


def _widest_gap(key, seed, config, served, tr, *, control):
    """Widest gap, over a sample of served requests, between the
    reference's best logit and the logit of the served (or, under
    ``control``, the float8 copy's first) token at each position."""
    rng = np.random.default_rng([seed % 2 ** 64, 3])
    rids = sorted(served)
    longest = max(rids, key=lambda r: len(served[r][1]))
    rest = [r for r in rids if r != longest]
    k = min(len(rest), int(tr["check_requests"]) - 1)
    pick = [longest] + ([rest[i] for i in rng.choice(len(rest), k,
                                                     replace=False)]
                        if k else [])
    length = int(tr["max_len"])
    toks = np.zeros((int(tr["check_requests"]), length), np.int32)
    mask = np.zeros(toks.shape, bool)    # next token was served
    for i, rid in enumerate(pick):
        prompt, out = served[rid]
        seq = np.concatenate([prompt, np.asarray(out, np.int32)])
        toks[i, :len(seq)] = seq
        mask[i, len(prompt) - 1:len(seq) - 1] = True
    want = phi3.logits(key, toks, config)
    nxt = jnp.asarray(np.roll(toks, -1, axis=1))
    if control:
        ctrl = phi3.logits(key, toks, config, control=True)
        nxt = jnp.argmax(ctrl, axis=-1).astype(jnp.int32)
        del ctrl
    chosen = jnp.take_along_axis(want, nxt[..., None], axis=-1)[..., 0]
    gaps = np.asarray(jnp.max(want, axis=-1) - chosen)[mask]
    print(f"compared {gaps.size} served tokens of {len(pick)} requests; "
          f"{int(np.sum(gaps == 0))} are the reference's first",
          file=sys.stderr)
    return float(np.max(gaps))
