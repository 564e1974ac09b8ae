"""Deterministic fault injection for the block-space runtime.

A :class:`FaultPlan` is a seeded, replayable schedule of faults keyed
by *call site* and *call index*: the same seed injects the same faults
at the same points of the same program, so every chaos run -- local,
CI, or a bug reproduction -- is a deterministic experiment, mirroring
how the plan verifier's mutation tests seed one fault class at a time.

:class:`ChaosInjector` realizes a plan at four layers:

Pallas layer (rides the PR 7 ``set_emit_hook``/``EmitRecord`` machinery
of :mod:`repro.core.backend`; interpreted launches only, like the
access sanitizer):

* ``corrupt_table`` -- perturb the decoded :class:`BlockCoords` of one
  grid step, exactly what a corrupted LUT/neighbour-table row would
  decode to (the block lands in / reads from the wrong place);
* ``poison_tile``   -- overwrite one output tile after the kernel body
  with NaN / inf / a sign-flip ("bitflip": finite garbage that only a
  spot-check catches, not the NaN screen).

Collective layer (a ``jax.lax.ppermute`` shim, counted per traced
call):

* ``drop_halo``  -- one halo-exchange round delivers zeros;
* ``delay_halo`` -- one round is applied twice (stale/wrong-source
  ghost rows).

Host layer (``wrap(site, fn)`` around prefill/decode/train steps):

* ``transient_error`` -- raise a transient fault (``mode="jax"``
  raises a real ``jax.errors.JaxRuntimeError``);
* ``fatal_error``     -- raise a ValueError (mis-shaped/compile
  family: must NOT be retried);
* ``poison_result``   -- NaN out every float leaf of the step's output
  (a NaN-producing tile surfacing at the step boundary);
* ``sigterm``         -- deliver SIGTERM to the process mid-step (a
  :class:`~repro.distributed.fault_tolerance.PreemptionGuard` must be
  installed, as serve/train do).

File layer (module functions): :func:`tear_checkpoint` truncates the
latest checkpoint and leaves a torn ``.tmp`` directory behind;
:func:`corrupt_tune_cache` plants a malformed winner entry.

Because Pallas/collective faults are baked into a *trace*, jit cache
hits would replay old faults against a stale call count;
:meth:`ChaosInjector.refresh` (and context entry/exit) clears jax
caches so every instrumented launch re-traces against the live
schedule -- guards pass it as ``before_retry``.

``python -m repro.runtime.chaos --matrix`` runs the chaos matrix: one
scenario per fault class, each asserting the fault is *detected* and
then either *recovered* (bit-identical to the fault-free run) or
*reported* (structured machine-readable failure report) -- the runtime
mirror of ``python -m repro.analysis.verify --matrix``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import signal
import sys
import tempfile
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_lib

from .guard import (Backoff, GuardedCall, GuardExhausted, TransientFault,
                    spot_check, validate_finite)

#: every fault class the harness can inject, by layer.
PALLAS_FAULTS = ("corrupt_table", "poison_tile")
COLLECTIVE_FAULTS = ("drop_halo", "delay_halo")
HOST_FAULTS = ("transient_error", "fatal_error", "poison_result",
               "sigterm")
FILE_FAULTS = ("torn_checkpoint", "corrupt_tune_cache")
ALL_FAULTS = PALLAS_FAULTS + COLLECTIVE_FAULTS + HOST_FAULTS + FILE_FAULTS

#: the reserved site names of the non-host layers.
PALLAS_SITE = "pallas"
PPERMUTE_SITE = "ppermute"


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One scheduled fault.

    kind:  one of :data:`ALL_FAULTS`.
    site:  call-site name -- :data:`PALLAS_SITE` (per instrumented
           emission), :data:`PPERMUTE_SITE` (per traced ppermute), or
           any host site a caller wraps (``"serve.decode"``, ...).
    index: 0-based call index at that site.
    mode:  kind-specific variant (poison: nan|inf|bitflip;
           transient_error: ""|jax).
    step:  grid-step selector for Pallas faults (which step of the
           launch is corrupted).
    rung:  when set, the fault only fires while the caller reports
           this degradation-ladder rung (persistent rung-0 failures
           that vanish after step-down).
    """

    kind: str
    site: str
    index: int
    mode: str = ""
    step: int = 0
    rung: Optional[int] = None

    def __post_init__(self):
        if self.kind not in ALL_FAULTS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {ALL_FAULTS}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class FaultPlan:
    """A replayable per-call-site fault schedule.

    Either list the faults explicitly or derive the whole schedule
    from one seed (:meth:`from_seed`); ``to_json``/``from_json`` make
    a plan portable into a bug report.
    """

    def __init__(self, seed: int, faults: Sequence[FaultSpec] = ()):
        self.seed = int(seed)
        self.faults = list(faults)

    @classmethod
    def from_seed(cls, seed: int, *, sites: Sequence[str],
                  kinds: Sequence[str] = ("transient_error",
                                          "poison_result"),
                  n_faults: int = 4, horizon: int = 16,
                  modes: Sequence[str] = ("", "jax")) -> "FaultPlan":
        """Derive a randomized-but-deterministic schedule: ``n_faults``
        faults drawn over ``sites x kinds x [0, horizon)`` from a
        generator seeded with ``seed`` alone."""
        rng = np.random.default_rng(seed)
        seen, faults = set(), []
        for _ in range(n_faults * 4):
            if len(faults) >= n_faults:
                break
            site = sites[int(rng.integers(len(sites)))]
            kind = kinds[int(rng.integers(len(kinds)))]
            index = int(rng.integers(horizon))
            if (site, index) in seen:
                continue
            seen.add((site, index))
            mode = ""
            if kind == "transient_error":
                mode = modes[int(rng.integers(len(modes)))]
            elif kind == "poison_tile":
                mode = ("nan", "inf", "bitflip")[int(rng.integers(3))]
            faults.append(FaultSpec(kind=kind, site=site, index=index,
                                    mode=mode))
        return cls(seed, faults)

    def for_call(self, site: str, index: int,
                 rung: Optional[int] = None) -> List[FaultSpec]:
        out = []
        for f in self.faults:
            if f.site != site or f.index != index:
                continue
            if f.rung is not None and rung is not None and f.rung != rung:
                continue
            out.append(f)
        return out

    def sites(self) -> set:
        return {f.site for f in self.faults}

    @property
    def has_traced_faults(self) -> bool:
        """True when the plan injects trace-baked (Pallas/collective)
        faults, i.e. retries must re-trace (``injector.refresh``)."""
        return any(f.site in (PALLAS_SITE, PPERMUTE_SITE)
                   for f in self.faults)

    def to_json(self) -> dict:
        return {"seed": self.seed,
                "faults": [f.to_json() for f in self.faults]}

    @classmethod
    def from_json(cls, d: dict) -> "FaultPlan":
        return cls(d["seed"], [FaultSpec(**f) for f in d["faults"]])


# ---------------------------------------------------------------------------
# injection
# ---------------------------------------------------------------------------

def _step_pred(plan, coords, step: int):
    """Predicate selecting one linear grid step of a launch (batch
    grid axes, when present, pinned to 0)."""
    if not coords.grid_ids:
        return None
    try:
        p = plan.linear_step(coords.grid_ids) == step
    except Exception:
        p = coords.grid_ids[-1] == step
    for g in coords.batch:
        p = p & (g == 0)
    return p


def _poison_value(val, mode: str):
    if not jnp.issubdtype(val.dtype, jnp.floating):
        return -val - 1
    if mode == "inf":
        return jnp.full_like(val, jnp.inf)
    if mode == "bitflip":
        # finite garbage: survives the NaN screen, only a spot check
        # (or the sanitizer) catches it
        return -val + jnp.asarray(1.0, val.dtype)
    return jnp.full_like(val, jnp.nan)


class ChaosInjector:
    """Realize a :class:`FaultPlan` against a live program.

    Use as a context manager around the workload: entry installs the
    emit hook (Pallas-layer faults), shims ``jax.lax.ppermute``
    (collective faults), and clears jit caches so instrumented
    launches re-trace; exit restores everything.  Host-layer faults
    need no context -- ``wrap(site, fn)`` consults the plan on every
    call.

    Call counters live on the injector and persist across traces: a
    retried launch consumes the *next* index, so a fault scheduled at
    one index fires exactly once.  ``events`` is the evidence trail
    (what fired, where, when).
    """

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.counters: collections.Counter = collections.Counter()
        self.events: List[dict] = []
        self._prev_hook = None
        self._orig_ppermute = None
        self._active = False

    # -- lifecycle -----------------------------------------------------------

    def __enter__(self) -> "ChaosInjector":
        self._prev_hook = backend_lib.set_emit_hook(self)
        self._orig_ppermute = jax.lax.ppermute
        jax.lax.ppermute = self._ppermute
        self._active = True
        jax.clear_caches()
        return self

    def __exit__(self, *exc):
        self._active = False
        backend_lib.set_emit_hook(self._prev_hook)
        jax.lax.ppermute = self._orig_ppermute
        jax.clear_caches()
        return False

    def refresh(self) -> None:
        """Drop cached executables so the next call re-traces against
        the live fault schedule (guards pass this as
        ``before_retry``)."""
        jax.clear_caches()

    # -- bookkeeping ---------------------------------------------------------

    def _count(self, site: str) -> int:
        idx = self.counters[site]
        self.counters[site] += 1
        return idx

    def _event(self, fault: FaultSpec, site: str, index: int,
               note: str = "") -> None:
        self.events.append({"kind": fault.kind, "site": site,
                            "index": index, "mode": fault.mode,
                            "note": note, "time": time.time()})

    # -- emit-hook protocol (Pallas layer) -----------------------------------

    def instrument(self, record, kernel, in_specs, out_specs):
        from repro.core.shard import ShardedPlan
        idx = self._count(PALLAS_SITE)
        faults = [f for f in self.plan.for_call(PALLAS_SITE, idx)
                  if f.kind in PALLAS_FAULTS]
        if not faults or isinstance(record.plan, ShardedPlan):
            # sharded launches trace once for all devices; a single
            # injection stream cannot be attributed to one device, so
            # Pallas faults are unsharded-only (collective faults
            # cover the sharded paths)
            return kernel, in_specs, out_specs
        from repro.core.plan import BlockCoords
        n_in = len(in_specs)
        for f in faults:
            self._event(f, PALLAS_SITE, idx, "instrumented")

        def kernel_chaos(coords, *refs):
            c = coords
            pred = _step_pred(record.plan, coords, faults[0].step)
            for f in faults:
                if f.kind == "corrupt_table" and pred is not None:
                    # what a corrupt LUT/neighbour row does: this
                    # step's block lands in the wrong place, i.e. its
                    # write never reaches the right tile -- model it
                    # by knocking the step's membership predicate out
                    # (a shifted-coords emulation is no good: the
                    # lambda map's self-similarity makes many wrong
                    # blocks mask-identical)
                    valid = ~pred if c.valid is None else c.valid & ~pred
                    c = BlockCoords(c.batch, c.bx, c.by, valid,
                                    c.first_step, c.grid_ids, c.refs)
            kernel(c, *refs)
            for f in faults:
                if f.kind == "poison_tile" and pred is not None \
                        and n_in < len(refs):
                    out_ref = refs[n_in]

                    def _poison(out_ref=out_ref, mode=f.mode):
                        out_ref[...] = _poison_value(out_ref[...], mode)

                    from jax.experimental import pallas as pl
                    pl.when(pred)(_poison)

        return kernel_chaos, in_specs, out_specs

    def wrap_call(self, record, fn):
        return fn

    # -- collective shim -----------------------------------------------------

    def _ppermute(self, x, axis_name, perm):
        idx = self._count(PPERMUTE_SITE)
        out = self._orig_ppermute(x, axis_name, perm)
        for f in self.plan.for_call(PPERMUTE_SITE, idx):
            if f.kind == "drop_halo":
                self._event(f, PPERMUTE_SITE, idx, "round dropped")
                out = jax.tree.map(jnp.zeros_like, out)
            elif f.kind == "delay_halo":
                self._event(f, PPERMUTE_SITE, idx, "round delayed")
                out = self._orig_ppermute(out, axis_name, perm)
        return out

    # -- host layer ----------------------------------------------------------

    def wrap(self, site: str, fn: Callable,
             rung: Optional[Callable[[], int]] = None) -> Callable:
        """Wrap a step function so scheduled host faults fire at their
        call index.  ``rung`` (a zero-arg callable) reports the current
        degradation-ladder level for rung-conditioned faults."""

        def call(*args, **kwargs):
            idx = self._count(site)
            r = rung() if rung is not None else None
            faults = self.plan.for_call(site, idx, rung=r)
            poison = None
            for f in faults:
                self._event(f, site, idx)
                if f.kind == "transient_error":
                    if f.mode == "jax":
                        raise _injected_jax_error(site, idx)
                    raise TransientFault(
                        f"chaos: injected transient fault at "
                        f"{site}#{idx}")
                if f.kind == "fatal_error":
                    raise ValueError(
                        f"chaos: injected fatal (shape-family) error "
                        f"at {site}#{idx}")
                if f.kind == "sigterm":
                    os.kill(os.getpid(), signal.SIGTERM)
                if f.kind == "poison_result":
                    poison = f
            out = fn(*args, **kwargs)
            if poison is not None:
                out = jax.tree.map(
                    lambda x: jnp.where(
                        jnp.ones_like(x) > 0, jnp.nan, x).astype(x.dtype)
                    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                    else x, out)
            return out

        return call


def _injected_jax_error(site: str, idx: int) -> Exception:
    """A *real* JaxRuntimeError (UNAVAILABLE family), so the guard's
    classifier is exercised against the genuine type."""
    try:
        from jax.errors import JaxRuntimeError
        return JaxRuntimeError(
            f"UNAVAILABLE: chaos: injected device loss at {site}#{idx}")
    except Exception:  # pragma: no cover
        return TransientFault(f"chaos: injected at {site}#{idx}")


# ---------------------------------------------------------------------------
# file-layer faults
# ---------------------------------------------------------------------------

def tear_checkpoint(directory: str, step: Optional[int] = None,
                    mode: str = "truncate") -> str:
    """Simulate a preemption mid-save: truncate the (latest) step's
    ``params.npz`` mid-file (``mode="truncate"``) or delete its
    ``meta.json`` (``mode="meta"``), and leave a torn ``.tmp``
    directory behind -- the exact debris an interrupted
    :meth:`~repro.checkpoint.manager.CheckpointManager.save` leaves.
    Returns the path of the torn step directory."""
    names = sorted(n for n in os.listdir(directory)
                   if n.startswith("step_") and not n.endswith(".tmp"))
    if step is not None:
        names = [n for n in names if int(n.split("_")[1]) == step]
    if not names:
        raise FileNotFoundError(f"no checkpoints to tear in {directory}")
    victim = os.path.join(directory, names[-1])
    npz = os.path.join(victim, "params.npz")
    if mode == "meta":
        os.unlink(os.path.join(victim, "meta.json"))
    else:
        size = os.path.getsize(npz)
        with open(npz, "rb") as f:
            head = f.read(max(1, size // 2))
        with open(npz, "wb") as f:
            f.write(head)
    # the half-written tmp dir of the save that never finished
    torn_tmp = victim + ".tmp"
    os.makedirs(torn_tmp, exist_ok=True)
    with open(os.path.join(torn_tmp, "params.npz"), "wb") as f:
        f.write(b"not a zipfile")
    return victim


def corrupt_tune_cache(path: str, kernel: str, params: dict) -> str:
    """Plant a malformed winner entry under the exact lookup key the
    kernels' ``grid_mode="auto"`` resolve uses: structurally valid
    JSON whose config is garbage (unknown lowering, non-integer fuse).
    Returns the corrupted key."""
    from repro.core.tune import TuneCache, _with_backend
    key = TuneCache.key(kernel, _with_backend(dict(params)))
    data = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        pass
    data[key] = {"config": {"lowering": "lambda-overflow",
                            "storage": "holographic",
                            "fuse": "many", "coarsen": -3},
                 "us": 0.0, "tuned_at": time.time()}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".chaos.tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(data, f)
    os.replace(tmp, path)
    return key


# ---------------------------------------------------------------------------
# the chaos matrix: one scenario per fault class
# ---------------------------------------------------------------------------

def _result(fault: str, status: str, **detail) -> dict:
    return {"fault": fault, "status": status, **detail}


def _no_backoff() -> Backoff:
    return Backoff(base_s=0.0, jitter=0.0)


def scenario_poison_tile(seed: int, smoke: bool) -> dict:
    """NaN-poisoned output tile -> NaN screen -> re-trace -> recover."""
    from repro.kernels.sierpinski_write import sierpinski_write
    n, block = (16, 4) if smoke else (32, 8)
    m = jnp.zeros((n, n), jnp.float32)

    def run():
        return sierpinski_write(m, 1.0, block=block,
                                grid_mode="closed_form", coarsen=1,
                                num_stages=1)

    clean = np.asarray(run())
    plan = FaultPlan(seed, [FaultSpec("poison_tile", PALLAS_SITE, 0,
                                      mode="nan")])
    with ChaosInjector(plan) as chaos:
        guard = GuardedCall(
            run, "write", retries=2, backoff=_no_backoff(),
            validators=[lambda o: validate_finite(o, "write output")],
            before_retry=chaos.refresh)
        out = np.asarray(guard())
    detected = any(e.kind == "validation" for e in guard.events)
    recovered = bool(np.array_equal(out, clean))
    if not chaos.events:
        return _result("poison_tile", "skipped",
                       reason="emit hook inactive (compiled backend)")
    status = "recovered" if (detected and recovered) else "failed"
    return _result("poison_tile", status, detected=detected,
                   bit_identical=recovered,
                   guard_events=[e.kind for e in guard.events])


def scenario_corrupt_table(seed: int, smoke: bool) -> dict:
    """Corrupt LUT row (wrong decoded block) -> spot check -> recover."""
    from repro.kernels.sierpinski_write import sierpinski_write
    n, block = (16, 4) if smoke else (32, 8)
    m = jnp.zeros((n, n), jnp.float32)

    def run():
        return sierpinski_write(m, 1.0, block=block,
                                grid_mode="prefetch_lut", coarsen=1,
                                num_stages=1)

    clean = np.asarray(run())
    plan = FaultPlan(seed, [FaultSpec("corrupt_table", PALLAS_SITE, 0,
                                      step=1)])
    with ChaosInjector(plan) as chaos:
        guard = GuardedCall(
            run, "write", retries=2, backoff=_no_backoff(),
            validators=[spot_check(clean, "lambda-plan spot check")],
            before_retry=chaos.refresh)
        out = np.asarray(guard())
    detected = any(e.kind == "validation" for e in guard.events)
    recovered = bool(np.array_equal(out, clean))
    if not chaos.events:
        return _result("corrupt_table", "skipped",
                       reason="emit hook inactive (compiled backend)")
    status = "recovered" if (detected and recovered) else "failed"
    return _result("corrupt_table", status, detected=detected,
                   bit_identical=recovered)


def scenario_drop_halo(seed: int, smoke: bool) -> dict:
    """A dropped halo ppermute round on an emulated mesh -> spot check
    -> re-trace -> recover."""
    if jax.device_count() < 2:
        return _result("drop_halo", "skipped",
                       reason=f"needs >= 2 devices, have "
                              f"{jax.device_count()} (set XLA_FLAGS="
                              f"--xla_force_host_platform_device_count)")
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.kernels.sierpinski_ca import ca_run
    n, block, steps = 32, 8, 4
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    dom = make_fractal_domain("sierpinski-gasket", n)
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    y, x = np.mgrid[0:n, 0:n]
    mask = np.asarray(dom.cell_member(x, y, n))
    rng = np.random.default_rng(0)
    emb = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                      .astype(np.float32))
    state = lay.pack(emb, block)
    buf = jnp.zeros_like(state)

    def run():
        return ca_run(state, buf, steps, fuse=2, rule="parity",
                      block=block, grid_mode="closed_form",
                      storage="compact", n=n, coarsen=1, num_stages=1,
                      donate=False, mesh=mesh, shard_axis="data")

    clean = np.asarray(run())
    plan = FaultPlan(seed, [FaultSpec("drop_halo", PPERMUTE_SITE, 0)])
    with ChaosInjector(plan) as chaos:
        guard = GuardedCall(
            run, "ca_sharded", retries=2, backoff=_no_backoff(),
            validators=[spot_check(clean, "halo spot check")],
            before_retry=chaos.refresh)
        out = np.asarray(guard())
    if not chaos.events:
        return _result("drop_halo", "skipped",
                       reason="no ppermute round executed")
    detected = any(e.kind == "validation" for e in guard.events)
    recovered = bool(np.array_equal(out, clean))
    status = "recovered" if (detected and recovered) else "failed"
    return _result("drop_halo", status, detected=detected,
                   bit_identical=recovered)


def _tiny_server(scfg=None, chaos=None, decode_kernel: str = ""):
    from repro.configs import get_config
    from repro.launch.serve import ServeConfig, Server
    from repro.models import init
    cfg = get_config("quickstart", smoke=True)
    if decode_kernel:
        cfg = cfg.replace(attn_decode_kernel=decode_kernel)
    params = init(jax.random.PRNGKey(0), cfg)
    scfg = scfg or ServeConfig(max_len=24, temperature=0.7, seed=11,
                               retries=3, backoff_base_s=0.0)
    return cfg, params, Server(cfg, params, scfg, chaos=chaos)


def scenario_transient_runtime(seed: int, smoke: bool) -> dict:
    """Injected JaxRuntimeError mid-decode -> classified transient ->
    retried -> token stream bit-identical to the fault-free run."""
    from repro.launch.serve import Server
    max_new = 4 if smoke else 6
    cfg, params, server = _tiny_server()
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8))
    ref = server.generate(prompts, max_new=max_new)

    plan = FaultPlan(seed, [
        FaultSpec("transient_error", "serve.decode", 1, mode="jax"),
        FaultSpec("transient_error", "serve.prefill", 0)])
    chaos = ChaosInjector(plan)
    faulty = Server(cfg, params, server.scfg, chaos=chaos)
    out = faulty.generate(prompts, max_new=max_new)
    detected = len(chaos.events) >= 2
    recovered = bool(np.array_equal(out, ref))
    status = "recovered" if (detected and recovered) else "failed"
    return _result("transient_error", status, detected=detected,
                   bit_identical=recovered,
                   injected=len(chaos.events))


def scenario_torn_checkpoint(seed: int, smoke: bool) -> dict:
    """Torn checkpoint dir -> restore falls back to the previous good
    step; an explicitly requested torn step raises (reported)."""
    from repro.checkpoint.manager import CheckpointManager
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3)
        p1 = {"w": np.arange(8, dtype=np.float32)}
        p2 = {"w": np.arange(8, dtype=np.float32) * 2}
        mgr.save(1, p1)
        mgr.save(2, p2)
        tear_checkpoint(d)
        step, params, _, meta = mgr.restore(
            None, {"w": np.zeros(8, np.float32)})
        fell_back = step == 1 and np.array_equal(params["w"], p1["w"])
        skipped = meta.get("skipped_torn_steps") == [2]
        reported = False
        try:
            mgr.restore(2, {"w": np.zeros(8, np.float32)})
        except Exception:
            reported = True
        # a later save must clear the torn .tmp debris
        mgr.save(3, p2)
        debris = [n for n in os.listdir(d) if n.endswith(".tmp")]
    ok = fell_back and skipped and reported and not debris
    return _result("torn_checkpoint", "recovered" if ok else "failed",
                   fell_back=fell_back, skipped_recorded=skipped,
                   explicit_raises=reported, tmp_cleaned=not debris)


def scenario_corrupt_tune_cache(seed: int, smoke: bool) -> dict:
    """Malformed tune-cache winner -> lookup rejects it, kernel runs on
    defaults instead of crashing on garbage knobs."""
    from repro.core import tune
    from repro.kernels.sierpinski_ca import ca_run
    n, block = 16, 4
    params = tune.target_params(
        {"fractal": "sierpinski-gasket", "n": n, "block": block,
         "rule": "parity"}, None)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tune.json")
        old = os.environ.get(tune.CACHE_ENV)
        os.environ[tune.CACHE_ENV] = path
        try:
            corrupt_tune_cache(path, "ca", params)
            got = tune.best("ca", params,
                            default={"lowering": "closed_form"})
            rejected = got == {"lowering": "closed_form"}
            state = jnp.zeros((n, n), jnp.float32)
            out = ca_run(state, jnp.zeros_like(state), 1, fuse="auto",
                         block=block, grid_mode="auto", coarsen="auto",
                         num_stages=1, donate=False)
            ran = bool(np.isfinite(np.asarray(out)).all())
        finally:
            if old is None:
                os.environ.pop(tune.CACHE_ENV, None)
            else:
                os.environ[tune.CACHE_ENV] = old
    ok = rejected and ran
    return _result("corrupt_tune_cache",
                   "recovered" if ok else "failed",
                   entry_rejected=rejected, kernel_ran=ran)


def scenario_sigterm_mid_decode(seed: int, smoke: bool) -> dict:
    """SIGTERM mid-decode -> drain + decode-state checkpoint -> a new
    server elastic-restores and resumes to a bit-identical stream."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed.elastic import elastic_restore
    from repro.launch.serve import ServeConfig, Server
    from repro.models import abstract_init
    max_new = 6 if smoke else 8
    with tempfile.TemporaryDirectory() as d:
        # fault-free reference run (no decode checkpointing: the torn
        # run below must resume from ITS OWN checkpoints)
        cfg, params, server = _tiny_server(
            ServeConfig(max_len=24, temperature=0.7, seed=5,
                        retries=3, backoff_base_s=0.0))
        prompts = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (2, 8))
        ref = server.generate(prompts, max_new=max_new)

        pmgr = CheckpointManager(os.path.join(d, "params"), keep=1)
        pmgr.save(0, params)

        scfg = ServeConfig(max_len=24, temperature=0.7, seed=5,
                           retries=3, backoff_base_s=0.0,
                           ckpt_dir=os.path.join(d, "decode"),
                           ckpt_every=1)
        plan = FaultPlan(seed, [FaultSpec("sigterm", "serve.decode", 2)])
        chaos = ChaosInjector(plan)
        faulty = Server(cfg, params, scfg, chaos=chaos)
        partial = faulty.generate(prompts, max_new=max_new)
        drained = (faulty.state.value == "draining"
                   and partial.shape[1] < max_new)

        # "restart": restore params onto whatever mesh survives and
        # resume from the decode-state checkpoint
        mesh, _, params2, _ = elastic_restore(
            pmgr, abstract_init(cfg), cfg)
        successor = Server(cfg, params2, scfg, mesh=mesh)
        out = successor.resume()
        recovered = bool(np.array_equal(out, ref))
    status = "recovered" if (drained and recovered) else "failed"
    return _result("sigterm", status, drained=drained,
                   bit_identical=recovered,
                   resumed_tokens=int(out.shape[1]))


def scenario_fatal_report(seed: int, smoke: bool) -> dict:
    """A fatal (shape-family) error must NOT be retried: one attempt,
    classified fatal, structured report emitted."""

    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("chaos: injected fatal (shape mismatch)")

    guard = GuardedCall(bad, "train_step", retries=3,
                        backoff=_no_backoff())
    report = None
    try:
        guard()
    except GuardExhausted as e:
        report = e.report
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "failure_report.json")
        written = False
        if report is not None:
            report.write(path)
            with open(path) as f:
                written = json.load(f)["classification"] == "fatal"
    ok = (report is not None and report.classification == "fatal"
          and calls["n"] == 1 and written)
    return _result("fatal_error", "reported" if ok else "failed",
                   attempts=calls["n"],
                   classification=getattr(report, "classification", None))


def scenario_serve_randomized(seed: int, smoke: bool) -> dict:
    """The serve smoke: randomized transient/poison injection across
    prefill+decode; generation must complete with zero corrupted
    outputs, bit-identical to the fault-free run."""
    from repro.launch.serve import Server
    max_new = 6 if smoke else 10
    cfg, params, server = _tiny_server()
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 8))
    ref = server.generate(prompts, max_new=max_new)

    plan = FaultPlan.from_seed(
        seed, sites=("serve.decode", "serve.prefill"),
        kinds=("transient_error", "poison_result"),
        n_faults=3 if smoke else 4, horizon=max_new)
    chaos = ChaosInjector(plan)
    faulty = Server(cfg, params, server.scfg, chaos=chaos)
    out = faulty.generate(prompts, max_new=max_new)
    finite = bool(np.all(out >= 0))
    recovered = bool(np.array_equal(out, ref))
    status = "recovered" if (recovered and finite) else "failed"
    return _result("serve_randomized", status, bit_identical=recovered,
                   injected=len(chaos.events),
                   plan=plan.to_json())


MATRIX = (
    scenario_poison_tile,
    scenario_corrupt_table,
    scenario_drop_halo,
    scenario_transient_runtime,
    scenario_torn_checkpoint,
    scenario_corrupt_tune_cache,
    scenario_sigterm_mid_decode,
    scenario_fatal_report,
    scenario_serve_randomized,
)


def run_matrix(seed: int = 0, smoke: bool = False,
               only: Optional[Sequence[str]] = None,
               verbose: bool = True) -> List[dict]:
    results = []
    for fn in MATRIX:
        name = fn.__name__.replace("scenario_", "")
        if only and name not in only:
            continue
        try:
            r = fn(seed, smoke)
        except Exception as e:  # noqa: BLE001 - matrix must report
            r = _result(name, "failed", error=f"{type(e).__name__}: {e}")
        results.append(r)
        if verbose:
            extra = "" if r["status"] != "skipped" else \
                f" ({r.get('reason', '')})"
            print(f"  chaos {r['fault']}: {r['status']}{extra}")
    return results


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.runtime.chaos",
        description=__doc__.splitlines()[0])
    ap.add_argument("--matrix", action="store_true",
                    help="run the full fault-injection matrix")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="serve smoke under randomized injection only")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced problem sizes (CI gate)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="comma-separated scenario subset")
    ap.add_argument("--out", default=None,
                    help="write the JSON chaos report here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if not (args.matrix or args.serve_smoke):
        ap.error("nothing to do: pass --matrix or --serve-smoke")
    only = tuple(s for s in args.only.split(",") if s) or None
    if args.serve_smoke and not args.matrix:
        only = ("serve_randomized",)

    results = run_matrix(seed=args.seed, smoke=args.smoke, only=only,
                         verbose=not args.quiet)
    n_failed = sum(r["status"] == "failed" for r in results)
    n_skipped = sum(r["status"] == "skipped" for r in results)
    report = {
        "ok": n_failed == 0,
        "seed": args.seed,
        "backend": backend_lib.resolve(None).name,
        "devices": jax.device_count(),
        "num_scenarios": len(results),
        "num_failed": n_failed,
        "num_skipped": n_skipped,
        "results": results,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(f"chaos matrix: {len(results)} scenarios, "
          f"{n_failed} failed, {n_skipped} skipped "
          f"(backend {report['backend']}, {report['devices']} devices)")
    return 0 if n_failed == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
