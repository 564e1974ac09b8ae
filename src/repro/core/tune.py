"""Persisted autotuner over the block-space scheduling axes.

Navarro et al. ("Efficient GPU Thread Mapping on Embedded 2D Fractals",
2020) show the best realization of the fractal map is configuration
dependent: which of the lowerings wins flips with problem size, block
geometry and hardware.  This module searches the axes the execution
engine exposes -- ``lowering x storage x block x fuse x coarsen`` --
measures each viable candidate with the same wall-clock harness the
benchmarks use, and persists the winner to a JSON cache keyed by
``(kernel, domain, n, backend)`` so a serving process pays the search
once per configuration, ever.

Two consumption paths:

* explicit: ``autotune_ca / autotune_write / autotune_flash`` run the
  search and return the winning config dict (``--autotune`` on the
  examples and benchmarks);
* implicit: the kernel entry points accept ``grid_mode="auto"`` (and
  ``fuse="auto"`` / ``coarsen="auto"`` where they exist), which is a
  cache *lookup only* -- never a measurement -- falling back to the
  defaults when no tuned entry exists.  Lookup happens in the un-jitted
  entry wrappers so a fresh tuning run is picked up by the next call,
  not pinned by jit's static-argument cache.

The cache file defaults to ``~/.cache/repro-tune.json`` and is
overridden by the ``REPRO_TUNE_CACHE`` environment variable (CI points
it at a workspace path).  Writes are atomic (tmp + rename).
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Callable, Iterable, Optional, Sequence

import jax
import numpy as np

CACHE_ENV = "REPRO_TUNE_CACHE"

#: measurement defaults: enough to get a stable median without making
#: a full search take minutes in interpret mode.
MEASURE_WARMUP = 1
MEASURE_ITERS = 3


def default_cache_path() -> str:
    return os.environ.get(CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-tune.json")


def _pos_int(v, hi: int = 1 << 20) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and 0 < v <= hi


def _sane_config(config: dict) -> bool:
    """A cached winner is only trusted if every knob the kernels act on
    carries a value the tuner could actually have produced -- an
    unknown lowering / storage or a non-positive-integer schedule
    factor marks the entry corrupt (tampered file, version skew, torn
    write) and the lookup treats it as a miss so the kernel runs on
    defaults.  Keys outside the known-knob set are left alone: callers
    may cache richer configs (and tests cache synthetic ones)."""
    if not config:
        return False
    from repro.core.plan import LOWERINGS
    checks = {
        "lowering": lambda v: v in LOWERINGS,
        "storage": lambda v: v in ("embedded", "compact"),
        "fuse": _pos_int,
        "coarsen": _pos_int,
        "stages": _pos_int,
        "block_q": _pos_int,
        "block_k": _pos_int,
        "page_size": _pos_int,
    }
    for k, v in config.items():
        check = checks.get(k)
        if check is not None and not check(v):
            return False
    return True


class TuneCache:
    """JSON-persisted map from tuning key to winning config.

    Entries are ``{"config": {...}, "us": float, "tuned_at": epoch}``
    keyed by the sorted-JSON of ``{"kernel": ..., **params}``.  The
    backend is always part of ``params`` (a CPU winner must never leak
    onto TPU), enforced by :func:`autotune` / :func:`best` rather than
    trusted to callers.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data = None

    @staticmethod
    def key(kernel: str, params: dict) -> str:
        return json.dumps({"kernel": kernel, **params}, sort_keys=True)

    def _load(self) -> dict:
        if self._data is None:
            self._data = {}
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if isinstance(data, dict):
                    self._data = data
            except (OSError, ValueError):
                pass  # missing or corrupt cache == empty cache
        return self._data

    def get(self, kernel: str, params: dict) -> Optional[dict]:
        entry = self._load().get(self.key(kernel, params))
        if not isinstance(entry, dict) or not isinstance(
                entry.get("config"), dict):
            return None
        config = dict(entry["config"])
        if not _sane_config(config):
            return None  # corrupt / tampered entry reads as a miss
        return config

    def put(self, kernel: str, params: dict, config: dict, us: float,
            save: bool = True) -> None:
        self._load()[self.key(kernel, params)] = {
            "config": dict(config), "us": round(float(us), 2),
            "tuned_at": time.time()}
        if save:
            self.save()

    def save(self) -> None:
        """Merge-on-save: under an exclusive lock, re-read the file and
        union it with the in-memory entries (ours win on conflict)
        before the atomic write, so concurrent tuning/benchmark
        processes append to the cache instead of clobbering each
        other's entries.  A corrupt or partially-written file on disk
        merges as empty.  The flock closes the read-merge-write window;
        on platforms without fcntl the merge still narrows it to the
        dump itself."""
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        try:
            import fcntl
            lock = open(self.path + ".lock", "w")
            fcntl.flock(lock, fcntl.LOCK_EX)
        except (ImportError, OSError):
            lock = None
        try:
            ours = self._load()
            merged = {}
            try:
                with open(self.path) as f:
                    disk = json.load(f)
                if isinstance(disk, dict):
                    merged.update(disk)
            except (OSError, ValueError):
                pass
            merged.update(ours)
            self._data = merged
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tune.tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(merged, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
        finally:
            if lock is not None:
                lock.close()

    def __len__(self) -> int:
        return len(self._load())


_DEFAULT: Optional[TuneCache] = None


def default_cache() -> TuneCache:
    """Process-wide cache bound to the current default path (re-made
    when REPRO_TUNE_CACHE changes, so tests can redirect it)."""
    global _DEFAULT
    path = default_cache_path()
    if _DEFAULT is None or _DEFAULT.path != path:
        _DEFAULT = TuneCache(path)
    return _DEFAULT


def _with_backend(params: dict) -> dict:
    p = dict(params)
    p.setdefault("backend", jax.default_backend())
    return p


def target_params(params: dict, target) -> dict:
    """Qualify a tuning key with the emission target
    (:mod:`repro.core.backend`), so e.g. an interpreter winner never
    answers for the chip and vice versa.  ``target`` may be a
    BackendTarget, a name, or None (= the process default, resolved
    here).  The reference point is the bare *platform* default -- not
    the process default -- because the cache file is shared across
    processes: a run steered onto another target via ``set_default``
    must stamp its entries even though that target is its own default.
    True platform-default entries keep the unqualified key, so existing
    caches stay valid."""
    from . import backend as backend_lib
    target = backend_lib.resolve(target)
    if target == backend_lib.platform_default():
        return params
    return {**params, "target": target.name}


def shard_params(params: dict, mesh, shard_axis: str) -> dict:
    """Qualify a tuning key with the shard count a kernel will actually
    run at (``mesh.shape[shard_axis]``), so a single-device winner never
    answers for a sharded run and different shard counts never collide.
    Unsharded lookups (``mesh=None``) keep the unqualified key, so
    existing caches remain valid.  The kernel entry points route every
    ``"auto"`` resolve through this."""
    if mesh is None:
        return params
    return {**params, "devices": int(mesh.shape[shard_axis])}


def measure(fn: Callable, *args, warmup: int = MEASURE_WARMUP,
            iters: int = MEASURE_ITERS) -> float:
    """Median wall-clock microseconds per call (the benchmarks'
    ``time_fn``, re-stated here so the tuner has no benchmark-package
    dependency and hillclimb can reuse one measurement path)."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    if out is not None:
        jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(samples))


def _axis_distance(a: dict, b: dict) -> int:
    """How many knobs two configs disagree on (missing = default)."""
    return sum(1 for k in set(a) | set(b) if a.get(k) != b.get(k))


def autotune(kernel: str, params: dict, candidates: Iterable[dict],
             build: Callable[[dict], Callable], *,
             cache: Optional[TuneCache] = None, force: bool = False,
             warmup: int = MEASURE_WARMUP, iters: int = MEASURE_ITERS,
             verbose: bool = False, seed_config: Optional[dict] = None,
             verify: Optional[Callable[[dict], None]] = None):
    """Generic search: measure every viable candidate, persist the winner.

    ``build(config)`` returns a zero-arg measurable callable, or raises
    ValueError / NotImplementedError to declare the candidate inviable
    for this problem (e.g. fuse > supertile, coarsen on a non-fractal
    domain) -- inviable candidates are skipped, not errors.

    ``verify(config)``, when given, runs after ``build`` and before any
    measurement; raising ValueError (the plan verifier's
    ``PlanVerificationError`` is one) rejects the candidate so a plan
    that fails static analysis is never timed, let alone persisted as a
    winner.  The kernel-specific searchers wire this to
    :mod:`repro.analysis` via their ``verify=True`` flag.

    ``seed_config`` warm-starts the search from a related problem's
    winner (e.g. the D=1 cache entry seeding a D>1 search): only the
    seed and its one-knob neighbours are measured, seed first, instead
    of the full cross product.

    Returns ``(config, us, trials)`` where trials is the full
    [(config, us)] measurement log (the hillclimb table rides on it).
    """
    cache = cache if cache is not None else default_cache()
    params = _with_backend(params)
    if not force:
        hit = cache.get(kernel, params)
        if hit is not None:
            return hit, None, []
    candidates = list(candidates)
    if seed_config is not None:
        near = [c for c in candidates
                if _axis_distance(c, seed_config) <= 1]
        if near:
            near.sort(key=lambda c: _axis_distance(c, seed_config))
            if verbose:
                print(f"  warm-start from {seed_config}: measuring "
                      f"{len(near)} of {len(candidates)} candidates")
            candidates = near
    trials = []
    best_cfg, best_us = None, float("inf")
    for cfg in candidates:
        try:
            fn = build(cfg)
        except (ValueError, NotImplementedError) as e:
            if verbose:
                print(f"  skip {cfg}: {e}")
            continue
        if verify is not None:
            try:
                verify(cfg)
            except (ValueError, NotImplementedError) as e:
                if verbose:
                    print(f"  reject {cfg}: plan verification failed: {e}")
                continue
        us = measure(fn, warmup=warmup, iters=iters)
        trials.append((dict(cfg), us))
        if verbose:
            print(f"  {cfg} -> {us:.1f} us")
        if us < best_us:
            best_cfg, best_us = dict(cfg), us
    if best_cfg is None:
        raise ValueError(f"autotune({kernel}): no viable candidate "
                         f"for {params}")
    cache.put(kernel, params, best_cfg, best_us)
    return best_cfg, best_us, trials


def best(kernel: str, params: dict, default: Optional[dict] = None,
         cache: Optional[TuneCache] = None) -> Optional[dict]:
    """Cache lookup only (the ``grid_mode='auto'`` path): the tuned
    config for this (kernel, params, backend), or ``default``."""
    cache = cache if cache is not None else default_cache()
    hit = cache.get(kernel, _with_backend(params))
    return hit if hit is not None else default


# ---------------------------------------------------------------------------
# Kernel-specific search spaces + searchers.  Each synthesizes its own
# operands (random state masked to the fractal / random qkv), so callers
# only describe the problem; the returned config is then passed to the
# real entry points.
# ---------------------------------------------------------------------------

#: the full (unrestricted) storage axis.  A search restricted to a
#: subset gets its own cache key (see :func:`_axis_param`): its winner
#: prescribes a storage, so it must never answer -- or overwrite -- the
#: unrestricted key the kernels' ``grid_mode="auto"`` lookups use.
ALL_STORAGES = ("embedded", "compact")
ALL_FLASH_BLOCKS = (64, 128, 256)


def _axis_param(params: dict, name: str, value, full) -> dict:
    """Stamp a candidate-axis restriction into the cache key params
    when (and only when) it deviates from the full default axis."""
    if tuple(sorted(map(str, value))) != tuple(sorted(map(str, full))):
        params[name] = "+".join(sorted(map(str, value)))
    return params

def _fuse_axis(block: int, coarsen: int, max_fuse: int) -> Sequence[int]:
    """Fuse depths to try: powers of two up to min(max_fuse, supertile
    side) -- the fused halo ring must fit inside one neighbour tile."""
    out, f = [], 1
    while f <= min(max_fuse, block * coarsen):
        out.append(f)
        f *= 2
    return out


def _coarsen_axis(fractal: str, n: int, block: int,
                  max_coarsen: int) -> Sequence[int]:
    from . import fractal as F
    m = 2 if fractal in ("sierpinski", "sierpinski-gasket") \
        else F.FRACTALS[fractal].m
    out, s = [], 1
    while s <= max_coarsen and (n // block) % s == 0 and s < n // block:
        out.append(s)
        s *= m
    return out or [1]


def _lowering_axis() -> tuple:
    """:data:`~repro.core.plan.LOWERINGS`, with ``mma`` hoisted to the
    front, where the MXU makes the digit-basis decode profitable:
    candidate order is measurement order, so the likely winner warms
    the jit caches first and the sharded warm-start explores its
    one-knob neighbourhood."""
    from .plan import LOWERINGS
    return ("mma",) + tuple(lo for lo in LOWERINGS if lo != "mma")


def ca_candidates(fractal: str, n: int, block: int, *,
                  storages=("embedded", "compact"), max_fuse: int = 8,
                  max_coarsen: int = 4):
    # pipelining depth: the synchronous launch or the DMA double buffer
    for storage in storages:
        for lowering in _lowering_axis():
            for coarsen in _coarsen_axis(fractal, n, block, max_coarsen):
                for fuse in _fuse_axis(block, coarsen, max_fuse):
                    for stages in (1, 2):
                        yield {"lowering": lowering, "storage": storage,
                               "fuse": fuse, "coarsen": coarsen,
                               "stages": stages}


def autotune_ca(*, fractal: str = "sierpinski-gasket", n: int = 256,
                block: int = 16, rule: str = "parity", steps: int = 8,
                storages=ALL_STORAGES, max_fuse: int = 8,
                max_coarsen: int = 4, cache: Optional[TuneCache] = None,
                force: bool = False, interpret: Optional[bool] = None,
                verbose: bool = False, backend=None, mesh=None,
                shard_axis: str = "data", verify: bool = False):
    """Search the CA scheduling axes for (fractal, n, block, rule).

    ``mesh=`` tunes the *sharded* run (shard-count-qualified cache
    key), warm-started from the D=1 winner when one is cached: only the
    D=1 config and its one-knob neighbours are re-measured instead of
    the full cross product (the fuse/coarsen landscape moves little
    with D; the lowering sometimes flips).  ``backend=`` tunes a
    non-default emission target under its own qualified key.
    ``verify=True`` statically verifies each candidate's GridPlan
    (:mod:`repro.analysis`) before it is measured; failing candidates
    are rejected from the search."""
    from .compact import compact_layout
    from .domain import make_fractal_domain
    from repro.kernels.sierpinski_ca import ca_run

    dom = make_fractal_domain(fractal, n // block)
    mask = np.zeros((n, n), bool)
    y, x = np.mgrid[0:n, 0:n]
    mask[:] = np.asarray(dom.cell_member(x, y, n))
    rng = np.random.default_rng(0)
    state = (rng.integers(0, 2, (n, n)) * mask).astype(np.float32)
    import jax.numpy as jnp
    operands = {"embedded": (jnp.asarray(state), jnp.zeros((n, n),
                                                           jnp.float32))}
    if "compact" in storages:
        lay = compact_layout(dom)
        operands["compact"] = (lay.pack(operands["embedded"][0], block),
                               lay.pack(operands["embedded"][1], block))

    def build(cfg):
        a, b = operands[cfg["storage"]]

        def fn():
            return ca_run(a, b, steps, rule=rule, block=block,
                          grid_mode=cfg["lowering"],
                          storage=cfg["storage"], n=n, fuse=cfg["fuse"],
                          coarsen=cfg["coarsen"],
                          num_stages=cfg.get("stages", 1),
                          backend=backend, interpret=interpret,
                          donate=False, mesh=mesh,
                          shard_axis=shard_axis)
        return fn

    vfy = None
    if verify:
        def vfy(cfg):
            a, b = operands[cfg["storage"]]
            # steps == fuse: a single fused launch traces (and thereby
            # verifies) the exact plan the measured config runs.
            ca_run(a, b, cfg["fuse"], rule=rule, block=block,
                   grid_mode=cfg["lowering"], storage=cfg["storage"],
                   n=n, fuse=cfg["fuse"], coarsen=cfg["coarsen"],
                   num_stages=cfg.get("stages", 1), backend=backend,
                   interpret=interpret, donate=False, mesh=mesh,
                   shard_axis=shard_axis, verify=True)

    base = _axis_param(
        {"fractal": fractal, "n": n, "block": block, "rule": rule},
        "storages", storages, ALL_STORAGES)
    base = target_params(base, backend)
    params = shard_params(base, mesh, shard_axis)
    seed = None
    if mesh is not None:
        # warm-start the D>1 search from the single-device winner
        seed = best("ca", base, cache=cache)
    cands = ca_candidates(fractal, n, block, storages=storages,
                          max_fuse=max_fuse, max_coarsen=max_coarsen)
    return autotune("ca", params, cands, build, cache=cache, force=force,
                    verbose=verbose, seed_config=seed, verify=vfy)


def write_candidates(fractal: str, n: int, block: int, *,
                     storages=("embedded", "compact"),
                     max_coarsen: int = 4):
    for storage in storages:
        for lowering in _lowering_axis():
            for coarsen in _coarsen_axis(fractal, n, block, max_coarsen):
                yield {"lowering": lowering, "storage": storage,
                       "coarsen": coarsen}


def autotune_write(*, fractal: str = "sierpinski-gasket", n: int = 256,
                   block: int = 16, storages=ALL_STORAGES,
                   max_coarsen: int = 4,
                   cache: Optional[TuneCache] = None, force: bool = False,
                   interpret: Optional[bool] = None,
                   verbose: bool = False, backend=None, mesh=None,
                   shard_axis: str = "data", verify: bool = False):
    """Search lowering x storage x coarsen for the write microbenchmark
    (``mesh``/``backend``/``verify`` as in :func:`autotune_ca`, incl.
    the D=1 warm start)."""
    from .compact import compact_layout
    from .domain import make_fractal_domain
    from repro.kernels.sierpinski_write import sierpinski_write
    import jax.numpy as jnp

    dom = make_fractal_domain(fractal, n // block)
    operands = {"embedded": jnp.zeros((n, n), jnp.float32)}
    if "compact" in storages:
        operands["compact"] = compact_layout(dom).pack(
            operands["embedded"], block)

    def build(cfg):
        m = operands[cfg["storage"]]

        def fn():
            return sierpinski_write(m, 1.0, block=block,
                                    grid_mode=cfg["lowering"],
                                    storage=cfg["storage"], n=n,
                                    coarsen=cfg["coarsen"],
                                    backend=backend, interpret=interpret,
                                    mesh=mesh, shard_axis=shard_axis)
        return fn

    vfy = None
    if verify:
        def vfy(cfg):
            sierpinski_write(operands[cfg["storage"]], 1.0, block=block,
                             grid_mode=cfg["lowering"],
                             storage=cfg["storage"], n=n,
                             coarsen=cfg["coarsen"], backend=backend,
                             interpret=interpret, mesh=mesh,
                             shard_axis=shard_axis, verify=True)

    base = _axis_param({"fractal": fractal, "n": n, "block": block},
                       "storages", storages, ALL_STORAGES)
    base = target_params(base, backend)
    params = shard_params(base, mesh, shard_axis)
    seed = best("write", base, cache=cache) if mesh is not None else None
    cands = write_candidates(fractal, n, block, storages=storages,
                             max_coarsen=max_coarsen)
    return autotune("write", params, cands, build, cache=cache,
                    force=force, verbose=verbose, seed_config=seed,
                    verify=vfy)


def flash_candidates(sq: int, sk: int, *, blocks=ALL_FLASH_BLOCKS):
    """lowering x block geometry for the flash-attention kernel."""
    for lowering in _lowering_axis():
        for b in blocks:
            if b <= min(sq, sk) and sq % b == 0 and sk % b == 0:
                yield {"lowering": lowering, "block_q": b, "block_k": b}


def autotune_flash(*, kind: str = "causal", batch: int = 1, heads: int = 4,
                   kv_heads: Optional[int] = None, sq: int = 1024,
                   sk: Optional[int] = None, d: int = 64, window: int = 0,
                   blocks=(64, 128, 256), cache: Optional[TuneCache] = None,
                   force: bool = False, interpret: Optional[bool] = None,
                   verbose: bool = False, backend=None,
                   verify: bool = False):
    """Search lowering x block geometry for the flash-attention kernel.
    ``verify=True`` statically verifies each candidate's plan before
    measuring it (:mod:`repro.analysis`)."""
    from repro.kernels.flash_attention import flash_attention
    import jax.numpy as jnp

    sk = sq if sk is None else sk
    kv_heads = heads if kv_heads is None else kv_heads
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, heads, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(batch, kv_heads, sk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, kv_heads, sk, d)), jnp.float32)

    def build(cfg):
        def fn():
            return flash_attention(q, k, v, kind=kind, window=window,
                                   block_q=cfg["block_q"],
                                   block_k=cfg["block_k"],
                                   grid_mode=cfg["lowering"],
                                   backend=backend, interpret=interpret)
        return fn

    vfy = None
    if verify:
        def vfy(cfg):
            flash_attention(q, k, v, kind=kind, window=window,
                            block_q=cfg["block_q"],
                            block_k=cfg["block_k"],
                            grid_mode=cfg["lowering"],
                            backend=backend, interpret=interpret,
                            verify=True)

    params = target_params(_axis_param(
        {"kind": kind, "batch": batch, "heads": heads,
         "kv_heads": kv_heads, "sq": sq, "sk": sk, "d": d,
         "window": window},
        "blocks", blocks, ALL_FLASH_BLOCKS), backend)
    return autotune("flash", params,
                    flash_candidates(sq, sk, blocks=blocks),
                    build, cache=cache, force=force, verbose=verbose,
                    verify=vfy)


#: the full page-size axis the paged-decode search sweeps.  Page size
#: trades pool fragmentation (small pages waste less tail) against
#: gather granularity (large pages mean fewer LUT rows per step); like
#: the flash block geometry, the winner is configuration dependent.
ALL_PAGE_SIZES = (8, 16, 32, 64)


def paged_candidates(seq: int, *, page_sizes=ALL_PAGE_SIZES):
    """lowering x page_size for the paged decode kernel.  Page sizes
    larger than the sequence are inviable (a one-page pool degenerates
    to the contiguous layout and is covered by the flash search)."""
    for lowering in _lowering_axis():
        for ps in page_sizes:
            if ps <= seq:
                yield {"lowering": lowering, "page_size": ps}


def autotune_paged(*, batch: int = 4, heads: int = 4,
                   kv_heads: Optional[int] = None, seq: int = 256,
                   d: int = 64, window: int = 0,
                   page_sizes=ALL_PAGE_SIZES,
                   cache: Optional[TuneCache] = None, force: bool = False,
                   interpret: Optional[bool] = None, verbose: bool = False,
                   backend=None, mesh=None, shard_axis: str = "data",
                   verify: bool = False):
    """Search lowering x page_size for the paged flash-decode kernel.

    Every candidate decodes the *same* logical caches: contiguous K/V
    are scattered into a fresh pool at each candidate's page size, so
    the measurement isolates the layout axis.  The page pool is sized
    to the candidate (``batch * ceil(seq/ps) + 1`` pages incl. the
    null page), matching what a serving process at that page size
    would hold live.  ``mesh=`` tunes the slot-sharded decode under a
    shard-count-qualified key (warm-started from the D=1 winner);
    ``verify=True`` statically verifies each candidate's paged plan
    before it is measured."""
    from repro.core import paged as paged_lib
    from repro.models.attention import decode_attention_paged
    import jax.numpy as jnp

    if interpret is not None:
        # the paged entry point has no interpret= knob of its own: the
        # emulation choice rides the resolved target
        from . import backend as backend_lib
        backend = backend_lib.resolve(backend, interpret)
    kv_heads = heads if kv_heads is None else kv_heads
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, heads, 1, d)), jnp.float32)
    k = rng.normal(size=(batch, kv_heads, seq, d)).astype(np.float32)
    v = rng.normal(size=(batch, kv_heads, seq, d)).astype(np.float32)
    pos = jnp.full((batch,), seq, jnp.int32)

    def operands(ps: int):
        npages = paged_lib.pages_for(seq, ps)
        pool = paged_lib.init_pool(batch * npages + 1, kv_heads, ps, d)
        table = np.full((batch, npages), paged_lib.NULL_PAGE, np.int32)
        for b_ in range(batch):
            pages = 1 + b_ * npages + np.arange(npages)
            table[b_] = pages
            pool = paged_lib.write_prefill_pages(
                pool, jnp.asarray(pages, jnp.int32), k[b_], v[b_])
        return pool, jnp.asarray(table)

    pools = {ps: operands(ps) for ps in page_sizes if ps <= seq}

    def build(cfg):
        pool, table = pools[cfg["page_size"]]

        def fn():
            return decode_attention_paged(
                q, pool, table, pos, window=window,
                grid_mode=cfg["lowering"], backend=backend,
                mesh=mesh, shard_axis=shard_axis)
        return fn

    vfy = None
    if verify:
        def vfy(cfg):
            pool, table = pools[cfg["page_size"]]
            decode_attention_paged(
                q, pool, table, pos, window=window,
                grid_mode=cfg["lowering"], backend=backend,
                mesh=mesh, shard_axis=shard_axis, verify=True)

    base = _axis_param(
        {"batch": batch, "heads": heads, "kv_heads": kv_heads,
         "seq": seq, "d": d, "window": window},
        "page_sizes", page_sizes, ALL_PAGE_SIZES)
    base = target_params(base, backend)
    params = shard_params(base, mesh, shard_axis)
    seed = best("paged", base, cache=cache) if mesh is not None else None
    return autotune("paged", params,
                    paged_candidates(seq, page_sizes=page_sizes),
                    build, cache=cache, force=force, verbose=verbose,
                    seed_config=seed, verify=vfy)


# ---------------------------------------------------------------------------
# CLI smoke: a deliberately tiny search so CI can exercise the full
# measure -> persist -> reload path in seconds (interpret mode).
# ---------------------------------------------------------------------------

def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny search space (CI)")
    ap.add_argument("--cache", default=None, help="cache file path")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even on a cache hit")
    args = ap.parse_args(argv)
    cache = TuneCache(args.cache) if args.cache else default_cache()
    if args.smoke:
        n, block, max_fuse, max_coarsen, blocks = 32, 8, 2, 2, (32,)
        sq, pseq, psizes = 64, 32, (8, 16)
    else:
        n, block, max_fuse, max_coarsen, blocks = 256, 16, 8, 4, (64, 128)
        sq, pseq, psizes = 512, 256, (16, 32, 64)
    for name, fn in (
        ("ca", lambda: autotune_ca(n=n, block=block, max_fuse=max_fuse,
                                   max_coarsen=max_coarsen, cache=cache,
                                   force=args.force, verbose=True)),
        ("write", lambda: autotune_write(n=n, block=block,
                                         max_coarsen=max_coarsen,
                                         cache=cache, force=args.force,
                                         verbose=True)),
        ("flash", lambda: autotune_flash(sq=sq, d=32, blocks=blocks,
                                         cache=cache, force=args.force,
                                         verbose=True)),
        ("paged", lambda: autotune_paged(batch=2, heads=2, seq=pseq,
                                         d=32, page_sizes=psizes,
                                         cache=cache, force=args.force,
                                         verbose=True)),
    ):
        cfg, us, trials = fn()
        tag = f"{us:.1f} us, {len(trials)} trials" if us is not None \
            else "cache hit"
        print(f"{name}: best={cfg} ({tag})")
    # reload through a fresh cache object to prove the persistence path
    fresh = TuneCache(cache.path)
    print(f"cache {cache.path}: {len(fresh)} entries")


if __name__ == "__main__":
    main()
