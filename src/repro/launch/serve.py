"""Guarded batched serving: prefill -> slot-based decode loop with
EOS-aware slot masking, replay-deterministic sampling, and a
detect-degrade-recover runtime around every jitted call.

Robustness model (see :mod:`repro.runtime`):

* every prefill/decode call runs under a
  :class:`~repro.runtime.guard.GuardedCall` -- per-call deadline,
  NaN/inf output screens, transient-vs-fatal classification, jittered
  backoff retries;
* sampling keys derive from ``(seed, slot, position)`` via
  ``jax.random.fold_in`` (pure coordinates, no mutated RNG state), so
  a retried or resumed decode step reproduces the identical stream;
* repeated failure walks a :class:`DegradationLadder`
  (blockspace -> xla decode, exotic lowering -> closed_form),
  re-jitting the decode step per rung and recording each transition;
* SIGTERM flips the state machine healthy -> draining: the decode
  state (prompts + generated tokens + position) checkpoints atomically
  and a successor process resumes mid-generation
  (:meth:`Server.resume`), bit-identical to an uninterrupted run;
* exhausted recovery emits a machine-readable
  :class:`~repro.runtime.guard.FailureReport`.

Runnable directly:
    PYTHONPATH=src python -m repro.launch.serve --arch quickstart
Chaos-smoke (deterministic fault injection; see repro.runtime.chaos):
    PYTHONPATH=src python -m repro.launch.serve --chaos-seed 7
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.distributed import sharding as shard_lib
from repro.distributed.fault_tolerance import PreemptionGuard
from repro.models import ModelConfig, decode_step, init, prefill
from repro.models import model as model_lib
from repro.runtime.guard import (Backoff, DegradationLadder, GuardedCall,
                                 GuardExhausted, ServerState, sample_key,
                                 spot_check, validate_finite)
from repro.runtime.trace import span


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256
    temperature: float = 0.0       # 0 = greedy
    top_k: int = 40
    seed: int = 0
    eos_id: int = -1               # -1 = never stop early
    # -- robustness ---------------------------------------------------------
    guard: bool = True             # False = raw jitted calls (no retries)
    retries: int = 3
    backoff_base_s: float = 0.05
    deadline_s: Optional[float] = None
    enforce_deadline: bool = False
    validate: bool = True          # NaN/inf screen on every output
    spot_check_every: int = 0      # decode steps between lambda canaries
    ckpt_dir: Optional[str] = None  # decode-state checkpoint directory
    ckpt_every: int = 0            # decode steps between checkpoints
    report_dir: Optional[str] = None  # failure reports land here


class Server:
    """Holds guarded jitted prefill/decode closures over a fixed batch
    shape, plus the serving state machine (healthy -> degraded ->
    draining) and the degradation ladder."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 mesh: Optional[Mesh] = None, chaos=None):
        self.cfg, self.params, self.scfg, self.mesh = cfg, params, scfg, mesh
        self.chaos = chaos
        self.state = ServerState.HEALTHY
        self.events: list = []
        self.ladder = DegradationLadder(
            self._rungs(cfg),
            on_transition=lambda rec: self.events.append(
                {"kind": "degrade", **rec}))
        self._base_key = jax.random.PRNGKey(scfg.seed)
        self._canary_ref = None
        self._ckpt = None
        if scfg.ckpt_dir:
            from repro.checkpoint.manager import CheckpointManager
            self._ckpt = CheckpointManager(scfg.ckpt_dir, keep=2)
        self._prefill_fn = jax.jit(
            partial(prefill, cfg=cfg, max_len=scfg.max_len))
        self._decode_fn = None
        self._apply_rung(self.ladder.current())
        self._prefill = self._guarded("serve.prefill",
                                      lambda *a: self._prefill_fn(*a))
        self._decode = self._guarded("serve.decode",
                                     lambda *a: self._decode_fn(*a))

    # -- degradation ladder --------------------------------------------------

    @staticmethod
    def _rungs(cfg: ModelConfig) -> list:
        """Fallback configs, as-configured first: blockspace decode
        degrades to the XLA decode path, an exotic attention lowering
        (compact / prefetch_lut / mma) degrades to the inline closed
        form."""
        top = {"decode_kernel": cfg.attn_decode_kernel,
               "grid_lowering": cfg.grid_lowering}
        rungs = [top]
        if cfg.attn_decode_kernel == "blockspace":
            rungs.append({**top, "decode_kernel": "xla"})
        if cfg.grid_lowering in ("compact", "prefetch_lut", "mma"):
            rungs.append({"decode_kernel": "xla",
                          "grid_lowering": "closed_form"})
        return rungs

    def _apply_rung(self, rung: dict) -> None:
        """Re-jit the decode step under this rung's config (prefill and
        the cache layout are rung-independent)."""
        cfg = self.cfg.replace(attn_decode_kernel=rung["decode_kernel"],
                               grid_lowering=rung["grid_lowering"])
        self._decode_fn = jax.jit(partial(decode_step, cfg=cfg))

    # -- guard plumbing ------------------------------------------------------

    def _guarded(self, site: str, fn):
        if self.chaos is not None:
            fn = self.chaos.wrap(site, fn, rung=lambda: self.ladder.level)
        if not self.scfg.guard:
            return fn
        validators = []
        if self.scfg.validate:
            validators.append(lambda o, s=site: validate_finite(o, s))
        return GuardedCall(
            fn, site, retries=self.scfg.retries,
            backoff=Backoff(base_s=self.scfg.backoff_base_s,
                            seed=self.scfg.seed),
            deadline_s=self.scfg.deadline_s,
            enforce_deadline=self.scfg.enforce_deadline,
            validators=validators,
            on_event=self.events.append,
            before_retry=(self.chaos.refresh if self.chaos is not None
                          else None))

    def _decode_step(self, tok, cache, pos):
        """One guarded decode step; on exhausted recovery, walk the
        degradation ladder and re-execute on the lower rung."""
        while True:
            try:
                return self._decode(self.params, tok, cache,
                                    jnp.asarray(pos, jnp.int32))
            except GuardExhausted as e:
                self._degrade_or_raise(e)

    def _degrade_or_raise(self, e: GuardExhausted) -> None:
        """Step down one rung after transient failures exhausted the
        retries.  A fatal error -- a kernel the compiler refuses, a
        shape bug -- is re-raised with its report instead: serving it
        from a lower rung would hide the broken path behind a healthy
        exit."""
        if (e.report.classification == "fatal"
                or not self.ladder.step_down(reason=str(e))):
            e.report.transitions = list(self.ladder.transitions)
            self._write_report(e.report)
            raise e
        self.state = ServerState.DEGRADED
        self._apply_rung(self.ladder.current())

    def _write_report(self, report) -> Optional[str]:
        if not self.scfg.report_dir:
            return None
        path = os.path.join(self.scfg.report_dir,
                            f"failure_{report.name.replace('.', '_')}.json")
        return report.write(path)

    # -- lambda canary -------------------------------------------------------

    def check_substrate(self) -> None:
        """Spot-check the Pallas substrate: rerun a tiny known-good
        block-space launch and demand a bit-identical result (the repo
        invariant).  Raises ValidationError on mismatch."""
        from repro.kernels.sierpinski_write import sierpinski_write

        def canary():
            return sierpinski_write(jnp.zeros((16, 16), jnp.float32), 1.0,
                                    block=4, grid_mode="closed_form",
                                    coarsen=1, num_stages=1)

        out = canary()
        if self._canary_ref is None:
            self._canary_ref = np.asarray(out)
            return
        spot_check(self._canary_ref, "lambda canary")(out)

    # -- sampling ------------------------------------------------------------

    def _sample(self, logits, pos: int):
        """logits (B,1,V) -> tokens (B,1).  Keys are a pure function of
        (seed, slot, position): a retried / replayed step samples the
        identical token."""
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits[:, 0], axis=-1)[:, None]
        scaled = logits[:, 0].astype(jnp.float32) / self.scfg.temperature
        if self.scfg.top_k:
            v, _ = jax.lax.top_k(scaled, self.scfg.top_k)
            scaled = jnp.where(scaled < v[:, -1:], -1e30, scaled)
        keys = sample_key(self._base_key, pos, scaled.shape[0])
        return jax.vmap(jax.random.categorical)(keys, scaled)[:, None]

    # -- decode-state checkpointing ------------------------------------------

    def _save_decode_state(self, prompts, out, pos: int,
                           max_new: int) -> None:
        if self._ckpt is None:
            return
        tokens = np.concatenate([np.asarray(t) for t in out], axis=1)
        state = {"prompts": np.asarray(prompts, np.int32),
                 "tokens": tokens.astype(np.int32)}
        self._ckpt.save(len(out), state,
                        extra={"pos": int(pos), "max_new": int(max_new),
                               "batch": int(tokens.shape[0]),
                               "prompt_len": int(np.shape(prompts)[1]),
                               "num_tokens": int(tokens.shape[1])})

    # -- generation ----------------------------------------------------------

    def generate(self, prompts: np.ndarray, max_new: int = 32):
        """prompts: (B, S) int tokens (token-input archs).  Returns the
        generated (B, T) continuation, T = max_new unless every slot
        hit ``eos_id`` (or a preemption drained the server) earlier;
        finished slots pad with ``eos_id``."""
        if self.state == ServerState.DRAINING:
            raise RuntimeError("server is draining; start a successor "
                               "and resume() from the decode checkpoint")
        scfg = self.scfg
        ctx = self.mesh if self.mesh is not None else _null()
        with PreemptionGuard() as preempt, ctx:
            logits, cache = self._prefill(self.params,
                                          jnp.asarray(prompts))
            batch = np.shape(prompts)[0]
            pos = np.shape(prompts)[1] - 1
            finished = np.zeros((batch,), bool)
            tok, finished = self._next_token(logits, pos, finished)
            out = [tok]
            for i in range(max_new - 1):
                if scfg.eos_id >= 0 and finished.all():
                    break
                if preempt.fired:
                    self._drain(prompts, out, pos, max_new)
                    break
                pos += 1
                logits, cache = self._decode_step(tok, cache, pos)
                tok, finished = self._next_token(logits, pos, finished)
                out.append(tok)
                if (scfg.spot_check_every
                        and (i + 1) % scfg.spot_check_every == 0):
                    self.check_substrate()
                if scfg.ckpt_every and len(out) % scfg.ckpt_every == 0:
                    self._save_decode_state(prompts, out, pos, max_new)
            else:
                if preempt.fired:
                    self._drain(prompts, out, pos, max_new)
        return np.asarray(jnp.concatenate(out, axis=1))

    def _next_token(self, logits, pos: int, finished: np.ndarray):
        """Sample, then overwrite finished slots with the EOS pad and
        fold newly-finished slots into the mask."""
        tok = self._sample(logits, pos)
        if self.scfg.eos_id < 0:
            return tok, finished
        tok = np.asarray(tok)
        tok = np.where(finished[:, None], self.scfg.eos_id, tok)
        finished = finished | (tok[:, 0] == self.scfg.eos_id)
        return jnp.asarray(tok), finished

    def _drain(self, prompts, out, pos: int, max_new: int) -> None:
        self.state = ServerState.DRAINING
        self.events.append({"kind": "drain", "pos": int(pos),
                            "tokens": len(out), "time": time.time()})
        self._save_decode_state(prompts, out, pos, max_new)

    # -- resume --------------------------------------------------------------

    def resume(self):
        """Resume a drained/preempted generation from the decode-state
        checkpoint: replay the saved tokens through prefill + decode to
        rebuild the KV cache (feeding the *saved* token at each replayed
        position -- no re-sampling, no drift), then keep sampling with
        the same (seed, slot, position) keys.  The full returned stream
        is bit-identical to an uninterrupted run."""
        if self._ckpt is None:
            raise RuntimeError("resume() needs ServeConfig.ckpt_dir")
        meta = self._ckpt.read_meta()
        e = meta["extra"]
        template = {
            "prompts": np.zeros((e["batch"], e["prompt_len"]), np.int32),
            "tokens": np.zeros((e["batch"], e["num_tokens"]), np.int32)}
        _, state, _, _ = self._ckpt.restore(meta["step"], template)
        prompts, saved = state["prompts"], np.asarray(state["tokens"])
        max_new = e["max_new"]
        ctx = self.mesh if self.mesh is not None else _null()
        with ctx:
            logits, cache = self._prefill(self.params,
                                          jnp.asarray(prompts))
            pos = prompts.shape[1] - 1
            finished = np.zeros((prompts.shape[0],), bool)
            out = []
            tok = jnp.asarray(saved[:, 0:1])
            out.append(tok)
            for i in range(1, saved.shape[1]):
                pos += 1
                logits, cache = self._decode_step(tok, cache, pos)
                tok = jnp.asarray(saved[:, i:i + 1])
                out.append(tok)
            if self.scfg.eos_id >= 0:
                finished = (saved == self.scfg.eos_id).any(axis=1)
            for _ in range(saved.shape[1], max_new):
                if self.scfg.eos_id >= 0 and finished.all():
                    break
                pos += 1
                logits, cache = self._decode_step(tok, cache, pos)
                tok, finished = self._next_token(logits, pos, finished)
                out.append(tok)
        self.state = ServerState.HEALTHY
        self.events.append({"kind": "resume", "replayed": saved.shape[1],
                            "total": len(out), "time": time.time()})
        return np.asarray(jnp.concatenate(out, axis=1))


# ---------------------------------------------------------------------------
# paged continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PagedServeConfig(ServeConfig):
    """ServeConfig plus the paged-pool knobs.  ``num_pages`` includes
    the reserved null page, so usable capacity is ``(num_pages - 1) *
    page_size`` tokens across all slots; ``max_len`` bounds one
    request's prompt + generation (it sizes the page table width, not
    any per-slot preallocation -- that is the whole point)."""
    num_slots: int = 4
    page_size: int = 16
    num_pages: int = 64


@dataclasses.dataclass
class _PagedRequest:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    pages: list = dataclasses.field(default_factory=list)
    next_pos: int = 0       # where the next fed token's KV lands
    seq: int = -1           # admission order (eviction priority)
    preemptions: int = 0


class PagedServer:
    """Continuous-batching serving over the paged KV pool.

    The decode batch is a fixed set of ``num_slots`` *slots* (static
    jitted shapes); requests stream through them.  Admission runs an
    unpadded prefill for one request, allocates ``ceil(len / page_size)``
    physical pages from the free list, and scatters the prefill KV into
    them (:func:`repro.models.model.scatter_prefill_pages`); every
    decode step then advances *all* active slots one token at their own
    positions (the per-row ``seq_pos`` vector) while inactive slots
    write to the null page.  Pages are allocated on demand as slots
    cross page boundaries; when the pool runs dry the youngest active
    request is preempted -- its pages freed, the request requeued with
    its generated tokens kept, to be re-admitted by replaying
    prompt + generated through prefill (recompute-style preemption).

    Sampling keys derive from ``(seed, request_id, position)``, so a
    preempted-and-readmitted request keeps drawing the same stream --
    eviction composes with the replay-deterministic robustness story of
    :class:`Server`.  Repeated decode failure walks the degradation
    ladder paged-blockspace -> paged-xla (the
    :func:`~repro.models.attention.decode_attention_paged_xla` gather
    rung), re-jitting the step like :meth:`Server._apply_rung` does;
    for latent (MLA) pools that rung is the XLA decode over the gathered
    latents (:func:`~repro.kernels.latent_decode.latent_decode_xla`).

    Under a held-experts share (``cfg.experts_held``) the decode step
    also returns the routes each held expert computed, per MoE layer;
    ``moe_routes_held`` keeps their sum for every step served.
    """

    _guarded = Server._guarded
    _write_report = Server._write_report
    _degrade_or_raise = Server._degrade_or_raise
    check_substrate = Server.check_substrate

    def __init__(self, cfg: ModelConfig, params, scfg: PagedServeConfig,
                 chaos=None):
        from repro.core import paged as paged_lib

        model_lib._check_paged(cfg)
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.chaos = chaos
        self.mesh = None
        self.state = ServerState.HEALTHY
        self.events: list = []
        # the pool's running aggregates over the decode steps served
        self.steps_served = 0
        self.fragmentation_sum = 0.0
        self.peak_utilization = 0.0
        self.moe_routes_held: list = []   # per step, held experts
        self._paged_lib = paged_lib
        self.alloc = paged_lib.PagedKVPool(scfg.num_pages, scfg.page_size)
        self.max_pages = -(-scfg.max_len // scfg.page_size)
        self.pools = model_lib.init_paged_cache(
            cfg, scfg.num_pages, scfg.page_size)
        self.table = np.full((scfg.num_slots, self.max_pages),
                             paged_lib.NULL_PAGE, np.int32)
        self.slots: list = [None] * scfg.num_slots
        self.pending: collections.deque = collections.deque()
        self.done: dict = {}
        self._admit_seq = 0
        self.ladder = DegradationLadder(
            self._rungs(cfg),
            on_transition=lambda rec: self.events.append(
                {"kind": "degrade", **rec}))
        self._base_key = jax.random.PRNGKey(scfg.seed)
        self._canary_ref = None
        self._prefill_fn = _jit_named(prefill, cfg=cfg)
        self._scatter_fn = _jit_named(model_lib.scatter_prefill_pages,
                                      cfg=cfg)
        self._decode_fn = None
        self._apply_rung(self.ladder.current())
        self._prefill = self._guarded("serve.prefill",
                                      lambda *a: self._prefill_fn(*a))
        self._decode = self._guarded("serve.decode",
                                     lambda *a: self._decode_fn(*a))

    @staticmethod
    def _rungs(cfg: ModelConfig) -> list:
        top = {"decode_kernel": cfg.attn_decode_kernel}
        rungs = [top]
        if cfg.attn_decode_kernel == "blockspace":
            rungs.append({"decode_kernel": "xla"})  # paged-xla gather
        return rungs

    def _apply_rung(self, rung: dict) -> None:
        cfg = self.cfg.replace(attn_decode_kernel=rung["decode_kernel"])
        self._decode_fn = _jit_named(model_lib.decode_step_paged, cfg=cfg)
        self.decode_grid_steps = self._grid_steps(cfg)

    def _grid_steps(self, cfg: ModelConfig) -> int:
        """Grid steps the block-indexed paged decode kernels run in one
        decode step under ``cfg``: the fused-KV kernel's grid times the
        attention layers plus the latent kernel's times the latent (MLA)
        layers (0 on the ``xla`` rung, where no layer runs either)."""
        if cfg.attn_decode_kernel != "blockspace":
            return 0
        from repro.core.paged import latent_lanes
        from repro.kernels.flash_attention import paged_decode_grid_steps
        from repro.kernels.latent_decode import latent_decode_grid_steps
        mixers = [model_lib.layer_sig(cfg, i)[0] for i in range(cfg.n_layers)]
        slots, pages = self.scfg.num_slots, self.max_pages
        return (paged_decode_grid_steps(slots, pages, mixers.count("attn"))
                + latent_decode_grid_steps(
                    slots, pages, mixers.count("mla"),
                    page_size=self.scfg.page_size,
                    width=latent_lanes(cfg.latent_width),
                    itemsize=jnp.dtype(cfg.jdtype()).itemsize))

    # -- host bookkeeping ----------------------------------------------------

    def _verify_table(self) -> None:
        if not self.scfg.validate:
            return
        from repro.analysis.verifier import verify_page_table
        verify_page_table(
            self.table,
            seq_lens=[(r.next_pos if r is not None else 0)
                      for r in self.slots],
            page_size=self.scfg.page_size,
            num_pages=self.scfg.num_pages,
            free_pages=self.alloc._free)

    def pool_stats(self) -> dict:
        return self.alloc.stats(
            [r.next_pos for r in self.slots if r is not None])

    @property
    def mean_fragmentation(self) -> float:
        """The pool's fragmentation after each decode step, averaged
        over the steps served (0 before the first)."""
        return (self.fragmentation_sum / self.steps_served
                if self.steps_served else 0.0)

    # -- request lifecycle ---------------------------------------------------

    def submit(self, rid: int, prompt: np.ndarray, max_new: int) -> None:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new > self.scfg.max_len:
            raise ValueError(
                f"request {rid}: prompt {len(prompt)} + max_new "
                f"{max_new} exceeds max_len {self.scfg.max_len}")
        self.pending.append(_PagedRequest(
            rid=int(rid), prompt=prompt, max_new=int(max_new)))

    def _sample_token(self, logits_row, rid: int, pos: int) -> int:
        """One token from a (V,) logits row.  The key is a pure
        function of (seed, request id, position): a preempted and
        re-admitted request draws the identical stream."""
        scfg = self.scfg
        if scfg.temperature <= 0:
            return int(np.argmax(np.asarray(logits_row)))
        scaled = np.asarray(logits_row, np.float32) / scfg.temperature
        if scfg.top_k:
            kth = np.sort(scaled)[-scfg.top_k]
            scaled = np.where(scaled < kth, -1e30, scaled)
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, rid), pos)
        return int(jax.random.categorical(key, jnp.asarray(scaled)))

    def _admit_one(self) -> bool:
        """Admit the head-of-line request if a slot and enough pages
        are free.  Returns True on admission."""
        if not self.pending:
            return False
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots:
            return False
        req = self.pending[0]
        tokens = np.concatenate(
            [req.prompt, np.asarray(req.out, np.int32)])
        need = self._paged_lib.pages_for(len(tokens), self.scfg.page_size)
        if not self.alloc.can_alloc(need):
            return False
        self.pending.popleft()
        with span("serve.admit", rid=req.rid, prompt_tokens=len(tokens),
                  pages=need, replayed=len(req.out)):
            return self._admit(req, tokens, self.alloc.alloc(need),
                               free_slots[0])

    def _admit(self, req: _PagedRequest, tokens: np.ndarray, pages: list,
               slot: int) -> bool:
        logits, caches = self._prefill(
            self.params, jnp.asarray(tokens[None]))
        with span("serve.scatter"):
            self.pools = self._scatter_fn(
                self.pools, caches, jnp.asarray(pages, jnp.int32))
        req.pages = list(pages)
        req.seq = self._admit_seq
        self._admit_seq += 1
        req.next_pos = len(tokens)
        self.table[slot] = self._paged_lib.NULL_PAGE
        self.table[slot, :len(pages)] = pages
        self.slots[slot] = req
        with span("serve.sample", slots=1):
            tok = self._sample_token(np.asarray(logits)[0, 0], req.rid,
                                     len(tokens) - 1)
        req.out.append(tok)
        with span("serve.table"):
            self._verify_table()
            if self._finished(slot, tok):
                return True
        self.events.append({"kind": "admit", "rid": req.rid,
                            "slot": slot, "pages": len(pages),
                            "replayed": len(req.out) - 1})
        return True

    def _finished(self, slot: int, tok: int) -> bool:
        req = self.slots[slot]
        if len(req.out) >= req.max_new or (
                self.scfg.eos_id >= 0 and tok == self.scfg.eos_id):
            self.alloc.free(req.pages)
            self.table[slot] = self._paged_lib.NULL_PAGE
            self.slots[slot] = None
            self.done[req.rid] = np.asarray(req.out, np.int32)
            self.events.append({"kind": "finish", "rid": req.rid,
                                "tokens": len(req.out),
                                "preemptions": req.preemptions})
            self._verify_table()
            return True
        return False

    def _preempt(self, slot: int) -> None:
        req = self.slots[slot]
        self.alloc.free(req.pages)
        req.pages = []
        req.preemptions += 1
        self.table[slot] = self._paged_lib.NULL_PAGE
        self.slots[slot] = None
        self.pending.appendleft(req)  # re-admit first
        self.events.append({"kind": "preempt", "rid": req.rid,
                            "slot": slot, "generated": len(req.out)})
        # no _verify_table here: surviving slots may already hold the
        # look-ahead page grown for the write this step, which the
        # verifier would flag as tail-null until next_pos advances.
        # step() verifies once the step is quiescent.

    def _grow(self, slot: int) -> bool:
        """Ensure the slot owns the page its next KV write lands in."""
        req = self.slots[slot]
        while req.next_pos // self.scfg.page_size >= len(req.pages):
            got = self.alloc.alloc(1)
            if got is None:
                return False
            self.table[slot, len(req.pages)] = got[0]
            req.pages += got
        return True

    def _decode_step(self, toks, table, posv, act):
        while True:
            try:
                return self._decode(self.params, toks, self.pools, table,
                                    posv, act)
            except GuardExhausted as e:
                self._degrade_or_raise(e)

    def _active(self) -> list:
        return [i for i in range(len(self.slots))
                if self.slots[i] is not None]

    def step(self) -> bool:
        """One decode step for every active slot.  Returns False when
        nothing is active."""
        active = self._active()
        if not active:
            return False
        with span("serve.step", step=self.steps_served,
                  active=len(active),
                  decode_grid_steps=self.decode_grid_steps) as step_span:
            return self._step(active, step_span)

    def _step(self, active: list, step_span) -> bool:
        with span("serve.grow"):
            preempted = self._grow_all(active)
        active = self._active()
        if not active:
            step_span.set_metadata(preempted=preempted)
            return False
        with span("serve.inputs"):
            B = self.scfg.num_slots
            toks = np.zeros((B, 1), np.int32)
            posv = np.zeros((B,), np.int32)
            act = np.zeros((B,), bool)
            for i in active:
                req = self.slots[i]
                toks[i, 0] = req.out[-1]
                posv[i] = req.next_pos
                act[i] = True
            inputs = (jnp.asarray(toks), jnp.asarray(self.table),
                      jnp.asarray(posv), jnp.asarray(act))
        logits, pools, *loads = self._decode_step(*inputs)
        with span("serve.release"):
            # frees the previous pools on the device, in a span of its
            # own so that the step's bookkeeping shows what it costs
            self.pools = pools
        # advance every slot before any finish check: the decode step
        # already wrote position next_pos for all of them, so a
        # mid-loop _verify_table must not see a stale next_pos
        moe = {}
        with span("serve.sample", slots=len(active)):
            logits = np.asarray(logits)
            if loads:
                loads = np.asarray(loads[0])
                moe = {"moe_routes_held": int(loads.sum()),
                       "moe_max_load": int(loads.max())}
                self.moe_routes_held.append(moe["moe_routes_held"])
            sampled = []
            for i in active:
                req = self.slots[i]
                tok = self._sample_token(logits[i, 0], req.rid,
                                         req.next_pos)
                req.next_pos += 1
                req.out.append(tok)
                sampled.append((i, tok))
        with span("serve.table"):
            for i, tok in sampled:
                self._finished(i, tok)
            self._verify_table()
            stats = self.pool_stats()
            self.steps_served += 1
            self.fragmentation_sum += stats["fragmentation"]
            self.peak_utilization = max(self.peak_utilization,
                                        stats["utilization"])
        step_span.set_metadata(
            preempted=preempted, pages_in_use=stats["used_pages"],
            free_pages=stats["free_pages"],
            live_tokens=stats["live_tokens"],
            alloc_tokens=stats["alloc_tokens"], **moe)
        return True

    def _grow_all(self, active: list) -> int:
        """On-demand page growth, oldest slots first; preempts the
        youngest active request until the survivors fit.  Returns the
        number preempted."""
        preempted = 0
        for i in sorted(active, key=lambda j: self.slots[j].seq):
            while self.slots[i] is not None and not self._grow(i):
                victims = self._active()
                victim = max(victims, key=lambda j: self.slots[j].seq)
                if victim == i and len(victims) == 1:
                    raise RuntimeError(
                        f"pool of {self.scfg.num_pages} pages cannot "
                        f"hold a single request; raise num_pages or "
                        f"page_size")
                self._preempt(victim)
                preempted += 1
        return preempted

    def run(self, requests, max_new: int = 32) -> dict:
        """Serve ``requests`` (a list of 1-D prompt token arrays) to
        completion.  Returns {rid: generated np.int32 array}."""
        for rid, prompt in enumerate(requests):
            self.submit(rid, prompt, max_new)
        while self.pending or any(s is not None for s in self.slots):
            while self._admit_one():
                pass
            if not self.step() and self.pending:
                raise RuntimeError(
                    "no active slots and the head-of-line request "
                    "cannot be admitted; pool too small")
        return self.done


def paged_throughput_report(server: PagedServer, requests,
                            max_new: int = 16) -> dict:
    t0 = time.perf_counter()
    out = server.run(requests, max_new=max_new)
    dt = time.perf_counter() - t0
    tokens = int(sum(len(v) for v in out.values()))
    return {"tokens": tokens, "seconds": dt, "tok_per_s": tokens / dt,
            "requests": len(out),
            "preemptions": sum(1 for e in server.events
                               if isinstance(e, dict)
                               and e.get("kind") == "preempt"),
            "mean_fragmentation": server.mean_fragmentation,
            "peak_utilization": server.peak_utilization}


def throughput_report(server: Server, batch: int, prompt_len: int,
                      max_new: int = 16):
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, server.cfg.vocab_size, (batch, prompt_len))
    t0 = time.perf_counter()
    out = server.generate(prompts, max_new=max_new)
    dt = time.perf_counter() - t0
    return {"tokens": int(out.size), "seconds": dt,
            "tok_per_s": out.size / dt}


def _jit_named(fn, **bound):
    """``jax.jit`` of ``fn`` with the keyword arguments ``bound`` fixed,
    under ``fn``'s own name: the trace shows the program as
    ``jit_<name>``, where a bare ``partial`` shows ``jit__unknown``."""
    bound_fn = partial(fn, **bound)
    bound_fn.__name__ = fn.__name__
    return jax.jit(bound_fn)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="quickstart")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config (default: "
                         "the published widths)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a slot early when it samples this token "
                         "(-1 = never)")
    ap.add_argument("--retries", type=int, default=3,
                    help="guarded-call retry budget per step")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-call deadline in seconds (recorded; "
                         "enforcement via ServeConfig)")
    ap.add_argument("--ckpt-dir", default="",
                    help="decode-state checkpoint directory (enables "
                         "preemption-safe draining + resume)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="serve under deterministic randomized fault "
                         "injection (repro.runtime.chaos) with this "
                         "seed -- the serving smoke CI runs")
    ap.add_argument("--grid-lowering", default="",
                    choices=("", "closed_form", "prefetch_lut", "bounding",
                             "mma", "compact"),
                    help="GridPlan lowering for the attention block "
                         "domain (default: the arch's attn_schedule)")
    ap.add_argument("--backend", default="",
                    choices=("", "tpu", "tpu-interpret", "interpret"),
                    help="kernel emission target for every block-space "
                         "Pallas call (repro.core.backend; default: "
                         "the platform's)")
    ap.add_argument("--decode-kernel", default="",
                    choices=("", "xla", "blockspace"),
                    help="decode attention path: 'blockspace' runs the "
                         "Pallas flash kernel with the run-time seq_pos "
                         "block skip, sharding continuous-batching slot "
                         "groups over the mesh (default: the arch's "
                         "setting, normally 'xla')")
    ap.add_argument("--mesh", default="",
                    help="serve on a device mesh: 'host' (all devices, "
                         "tp=1) or 'DATAxMODEL' (e.g. '4x2').  The same "
                         "mesh drives the sharding.py param/cache specs "
                         "and the block-space kernels' shard_axis "
                         "('data') -- one mesh for the whole process.")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV pool + continuous-"
                         "batching scheduler (PagedServer) instead of "
                         "the fixed-batch contiguous server; --batch "
                         "becomes the request count and prompts get "
                         "mixed lengths in [4, --prompt-len]")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="paged: concurrently decoding slots (the "
                         "static batch shape of the decode step)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per KV page (the autotuned "
                         "knob; see repro.core.tune.autotune_paged)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged: physical pages in the pool incl. the "
                         "reserved null page (0 = enough for num_slots "
                         "requests at max_len)")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import resolve_cli_mesh
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=True if args.smoke else None)
    # serving holds its weights in the compute dtype (bf16 for the
    # published configs): f32 master weights are a training concern
    cfg = cfg.replace(param_dtype=cfg.dtype)
    if args.grid_lowering:
        cfg = cfg.replace(grid_lowering=args.grid_lowering)
        print(f"grid lowering: {cfg.grid_mode} "
              f"(xla schedule: {cfg.attn_schedule_resolved})")
    if args.backend:
        from repro.core import backend as backend_lib
        backend_lib.set_default(args.backend)
        print(f"kernel backend: {backend_lib.resolve(None).name}")
    if args.decode_kernel:
        cfg = cfg.replace(attn_decode_kernel=args.decode_kernel)
        print(f"decode attention: {cfg.attn_decode_kernel}")
    mesh = resolve_cli_mesh(args.mesh)
    if cfg.attn_decode_kernel == "blockspace":
        from repro.models import attention as attn_lib
        attn_lib.set_decode_mesh(mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} "
              f"devices (kernels shard over axis 'data')")
        param_specs = shard_lib.param_spec_tree(
            model_lib.abstract_init(cfg), cfg)
        init_fn = jax.jit(
            partial(init, cfg=cfg),
            out_shardings=shard_lib.named_sharding_tree(param_specs,
                                                        mesh))
        with mesh:
            params = init_fn(jax.random.PRNGKey(0))
    else:
        params = init(jax.random.PRNGKey(0), cfg)
    chaos = None
    if args.chaos_seed is not None:
        from repro.runtime.chaos import ChaosInjector, FaultPlan
        plan = FaultPlan.from_seed(
            args.chaos_seed, sites=("serve.prefill", "serve.decode"),
            horizon=args.max_new)
        chaos = ChaosInjector(plan)
        print(f"chaos: {len(plan.faults)} faults scheduled "
              f"(seed {plan.seed})")
    if args.paged:
        from repro.core.paged import pages_for
        max_len = args.prompt_len + args.max_new
        num_pages = args.num_pages or (
            1 + args.num_slots * pages_for(max_len, args.page_size))
        server = PagedServer(cfg, params, PagedServeConfig(
            max_len=max_len, temperature=args.temperature,
            eos_id=args.eos_id, retries=args.retries,
            deadline_s=args.deadline,
            num_slots=args.num_slots, page_size=args.page_size,
            num_pages=num_pages), chaos=chaos)
        rng = np.random.default_rng(0)
        requests = [rng.integers(0, cfg.vocab_size,
                                 (int(rng.integers(4, args.prompt_len
                                                   + 1)),))
                    for _ in range(args.batch)]
        print(f"paged: {args.num_slots} slots, {num_pages} pages of "
              f"{args.page_size} tokens, {args.batch} mixed-length "
              f"requests")
        rep = paged_throughput_report(server, requests,
                                      max_new=args.max_new)
        if chaos is not None:
            print(f"chaos: {len(chaos.events)} faults fired, "
                  f"state {server.state.value}")
        print(rep)
        return
    server = Server(cfg, params, ServeConfig(
        max_len=args.prompt_len + args.max_new,
        temperature=args.temperature, eos_id=args.eos_id,
        retries=args.retries, deadline_s=args.deadline,
        ckpt_dir=args.ckpt_dir or None,
        ckpt_every=4 if args.ckpt_dir else 0), mesh=mesh, chaos=chaos)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len))
    out = server.generate(prompts, max_new=args.max_new)
    print("generated shape:", out.shape)
    if chaos is not None:
        recov = sum(getattr(g, "recoveries", 0)
                    for g in (server._prefill, server._decode))
        print(f"chaos: {len(chaos.events)} faults fired, "
              f"{recov} recoveries, state {server.state.value}")
        if not np.isfinite(np.asarray(out, np.float64)).all():
            raise SystemExit("chaos smoke: corrupted output escaped")
    print(throughput_report(server, args.batch, args.prompt_len,
                            args.max_new))


if __name__ == "__main__":
    main()
