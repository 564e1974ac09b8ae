"""Static plan verification: machine-checked proofs of the block-space
invariants, per emitted plan.

Given any :class:`~repro.core.plan.GridPlan` (or
:class:`~repro.core.shard.ShardedPlan`), every decode the kernels run --
``_decode``, ``storage_index``, ``neighbor_index``, ``_step_valid`` --
is also evaluable on host numpy arrays, so the verifier enumerates the
*entire* launch grid per device and checks, exhaustively:

``coverage``
    Every block of the scheduled domain is decoded by exactly one live
    grid step per launch (union over devices for sharded plans),
    against a ground-truth enumeration built only from
    ``domain.contains`` over the bounding box -- never from the decode
    path under test.

``race``
    The storage tile write-set is pairwise disjoint across live steps
    of one launch (per device): every visited output block is written
    back, so a storage-index collision lets one live step overwrite
    another's tile.

``table``
    Host-built decode tables -- the 28-column LUT, packed-slot and
    neighbour-slot tables, shard tables, ghost maps, HaloPlan rounds,
    phase tables -- are re-derived from ``linear_index`` /
    ``lambda_inverse`` / membership and diffed entry-by-entry.  The
    neighbour check is semantic: a neighbour slot must *invert* (via
    the slot -> coords table) to exactly the embedded neighbour.

``bounds``
    ``storage_index`` / ``neighbor_index`` are evaluated over **all**
    grid steps (dead and pad steps still drive index maps and DMA
    addressing) and the exact [min, max] hull per axis is checked
    against the operand tile grid.

``alias``
    ``input_output_aliases`` write-in-place: for each aliased input,
    its modelled read tiles at live step ``s`` must never intersect the
    output write tile of a different live step ``t`` (the CA
    double-buffer invariant -- the stale buffer is aliased but never
    read -- is what makes the 9-point stencil safe; aliasing the state
    instead is flagged).

``verify_plan`` runs everything applicable and returns a
:class:`Report`; ``verify_or_raise`` raises
:class:`PlanVerificationError` (a ``ValueError``, so autotune treats a
failing candidate as inviable) on any finding.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.compact import NEIGHBOR_OFFSETS8
from repro.core.plan import (_LUT_BX, _LUT_BY, _LUT_NBR, _LUT_SX,
                             _LUT_SY, GridPlan)
from repro.core.shard import SHARD_COUNT, SHARD_GMAP, SHARD_LO, ShardedPlan


class PlanVerificationError(ValueError):
    """A plan failed static verification.  Subclasses ``ValueError`` so
    :func:`repro.core.tune.autotune` rejects the candidate as inviable
    instead of measuring it."""


@dataclasses.dataclass
class Finding:
    """One verified invariant violation."""

    check: str                      # coverage|race|table|bounds|alias
    detail: str
    device: Optional[int] = None

    def __str__(self) -> str:
        where = f" [device {self.device}]" if self.device is not None \
            else ""
        return f"{self.check}{where}: {self.detail}"

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Report:
    """Result of one :func:`verify_plan` run."""

    plan: Dict[str, Any]
    checks: Tuple[str, ...]
    findings: List[Finding]

    @property
    def ok(self) -> bool:
        return not self.findings

    def raise_on_findings(self) -> "Report":
        if self.findings:
            lines = "\n  ".join(str(f) for f in self.findings)
            raise PlanVerificationError(
                f"plan verification failed for {self.plan}:\n  {lines}")
        return self

    def to_json(self) -> Dict[str, Any]:
        return {"plan": self.plan, "checks": list(self.checks),
                "ok": self.ok,
                "findings": [f.to_json() for f in self.findings]}


#: per-kernel access models: whether the storage write-set must be
#: race-free (reductions to per-step partials are exempt), whether the
#: kernel reads the 8 halo operands, and the read model of each aliased
#: input ("none" = never read, e.g. the CA stale buffer; "center" =
#: read at the step's own storage tile; "center+neighbors" = the
#: stencil gather).
ACCESS_MODELS: Dict[str, Dict[str, Any]] = {
    "generic": {"race": True, "neighbors": False, "storage": True,
                "alias_reads": ()},
    "write": {"race": True, "neighbors": False, "storage": True,
              "alias_reads": ("center",)},
    "sum": {"race": False, "neighbors": False, "storage": True,
            "alias_reads": ()},
    "ca": {"race": True, "neighbors": True, "storage": True,
           "alias_reads": ("none",)},
    "flash": {"race": False, "neighbors": False, "storage": False,
              "alias_reads": (), "hulls": True},
}


class HostMesh:
    """Geometry-only stand-in for ``jax.sharding.Mesh``: enough to
    build a :class:`ShardedPlan` for host-side verification (its tables
    and decodes never touch a device; only live ``ppermute`` traffic
    would need real devices)."""

    def __init__(self, num_shards: int, axis: str = "data"):
        self.shape = {axis: int(num_shards)}


# ---------------------------------------------------------------------------
# host evaluation helpers
# ---------------------------------------------------------------------------

def _np(x) -> np.ndarray:
    return np.asarray(x)


def _is_sharded(plan: GridPlan) -> bool:
    return isinstance(plan, ShardedPlan)


def _phase(plan: GridPlan):
    return getattr(plan, "phase", None)


def plan_signature(plan: GridPlan) -> Dict[str, Any]:
    sig: Dict[str, Any] = {
        "domain": plan.domain.name,
        "lowering": plan.lowering,
        "storage": plan.storage,
        "coarsen": plan.coarsen,
        "backend": plan.target.name,
    }
    if _is_sharded(plan):
        sig["shards"] = plan.num_shards
        sig["partition"] = plan.partition
        if plan.phase is not None:
            sig["phase"] = plan.phase
    return sig


def num_devices(plan: GridPlan) -> int:
    return plan.num_shards if _is_sharded(plan) else 1


def host_prefetch_refs(plan: GridPlan, device: int = 0) -> Tuple:
    """The decode-table operands device ``device``'s launch receives,
    as host numpy arrays (exactly what ``shard_map`` would slice)."""
    if not _is_sharded(plan):
        if plan.lowering == "prefetch_lut":
            return (np.asarray(plan.lut_host()),)
        if plan._table_backed:  # mma
            return (np.asarray(plan.mma_table_host()),)
        return ()
    refs: Tuple = (np.asarray(plan.shard_table_host()[device]),)
    if plan._table_backed:
        # per-device LUT chunk size is the *base* plan's steps_per_shard
        # (phase views indirect into the same chunk)
        if plan.partition == "storage-rows":
            per = plan.rpd * plan.ncols
        else:
            per = plan.steps_per_shard
        lut = plan.lut_sharded_host()
        if lut is None:
            lut = plan.mma_table_sharded_host()
        refs += (np.asarray(lut[device * per:(device + 1) * per]),)
    if plan.phase is not None:
        it, bt = plan.phase_tables_host()
        tab = it if plan.phase == "interior" else bt
        refs += (np.asarray(tab[device]),)
    return refs


def host_steps(plan: GridPlan) -> Tuple[np.ndarray, ...]:
    """Every grid-step id tuple of one launch as parallel numpy arrays
    (batch dims pinned to 0: the domain decode is batch-invariant)."""
    nb = len(plan.batch_dims)
    grid = plan.grid
    if plan.lowering == "bounding":
        nby, nbx = int(grid[nb]), int(grid[nb + 1])
        gy, gx = np.mgrid[0:nby, 0:nbx]
        dom: Tuple[np.ndarray, ...] = (gy.ravel().astype(np.int64),
                                       gx.ravel().astype(np.int64))
    else:
        dom = (np.arange(int(grid[nb]), dtype=np.int64),)
    zero = np.zeros_like(dom[0])
    return tuple(zero for _ in range(nb)) + dom


def decode_steps(plan: GridPlan, refs: Tuple,
                 ids: Optional[Tuple[np.ndarray, ...]] = None):
    """(ids, bx, by, live): the full host decode of one launch."""
    if ids is None:
        ids = host_steps(plan)
    _, bx, by = plan._decode(ids, refs)
    bx = _np(bx).astype(np.int64)
    by = _np(by).astype(np.int64)
    bx, by = np.broadcast_arrays(bx, by)
    if bx.shape != ids[-1].shape:
        bx = np.broadcast_to(bx, ids[-1].shape)
        by = np.broadcast_to(by, ids[-1].shape)
    valid = plan._step_valid(ids, bx, by, refs)
    if valid is None:
        live = np.ones(ids[-1].shape, bool)
    else:
        live = np.broadcast_to(_np(valid).astype(bool), ids[-1].shape)
    return ids, bx, by, live


def storage_tiles(plan: GridPlan, refs: Tuple,
                  ids: Tuple[np.ndarray, ...]):
    """(row, col) storage tile index per grid step, host-evaluated."""
    r, c = plan.storage_index(ids, refs)
    r = np.broadcast_to(_np(r).astype(np.int64), ids[-1].shape)
    c = np.broadcast_to(_np(c).astype(np.int64), ids[-1].shape)
    return r, c


def neighbor_tiles(plan: GridPlan, refs: Tuple,
                   ids: Tuple[np.ndarray, ...], j: int):
    r, c = plan.neighbor_index(j, ids, refs)
    r = np.broadcast_to(_np(r).astype(np.int64), ids[-1].shape)
    c = np.broadcast_to(_np(c).astype(np.int64), ids[-1].shape)
    return r, c


def members_host(domain) -> Tuple[np.ndarray, np.ndarray]:
    """Ground-truth member blocks from membership alone (independent of
    the enumeration/decode under test)."""
    nbx, nby = domain.bounding_box
    gy, gx = np.mgrid[0:nby, 0:nbx]
    gx = gx.astype(np.int64)
    gy = gy.astype(np.int64)
    if getattr(domain, "always_member", False):
        return gx.ravel(), gy.ravel()
    m = np.broadcast_to(_np(domain.contains(gx, gy)),
                        gx.shape).astype(bool)
    return gx[m], gy[m]


def storage_grid(plan: GridPlan) -> Tuple[int, int]:
    """(rows, cols) of the tile grid the *center* storage index
    addresses (the local slab for sharded compact plans)."""
    if _is_sharded(plan) and plan.storage == "compact":
        return plan.rpd, plan.ncols
    if plan.storage == "compact":
        scols, srows = plan.layout.grid_shape
        if plan._tiling is not None:
            bw, bh = plan._tiling.sub_shape
            return srows // bh, scols // bw
        return srows, scols
    nbx, nby = plan.sched_domain.bounding_box
    return nby, nbx


def neighbor_grid(plan: GridPlan) -> Tuple[int, int]:
    """(rows, cols) tile-grid bound for the halo operand indices: the
    halo-extended slab (ghost rows + dump) under sharded compact."""
    if _is_sharded(plan) and plan.storage == "compact":
        h_max = plan.halo.h_max if plan.halo is not None else 0
        return plan.rpd + h_max + 1, plan.ncols
    return storage_grid(plan)


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def _check_coverage(plan, per_device, findings):
    gx, gy = members_host(plan.sched_domain)
    truth = set(zip(gx.tolist(), gy.tolist()))
    seen: Dict[Tuple[int, int], int] = {}
    for d, (ids, bx, by, live) in enumerate(per_device):
        pts = list(zip(bx[live].tolist(), by[live].tolist()))
        local = set()
        for p in pts:
            if p in local:
                findings.append(Finding(
                    "coverage", f"block {p} decoded by two live steps "
                    f"of one launch", device=d))
            local.add(p)
            seen[p] = seen.get(p, 0) + 1
    extra = [p for p in seen if p not in truth]
    missing = [p for p in truth if p not in seen]
    double = [p for p, k in seen.items() if k > 1]
    for p in extra[:3]:
        findings.append(Finding(
            "coverage", f"live step decodes non-member block {p}"))
    for p in missing[:3]:
        findings.append(Finding(
            "coverage", f"member block {p} is never covered"))
    for p in double[:3]:
        findings.append(Finding(
            "coverage", f"member block {p} covered {seen[p]} times "
            f"across the mesh"))
    if len(extra) > 3 or len(missing) > 3 or len(double) > 3:
        findings.append(Finding(
            "coverage", f"... {len(extra)} extra / {len(missing)} "
            f"missing / {len(double)} multiply-covered blocks total"))


def _check_race(plan, refs_per_device, per_device, findings):
    for d, (ids, bx, by, live) in enumerate(per_device):
        r, c = storage_tiles(plan, refs_per_device[d], ids)
        keys = (r[live] * (c.max() + 2) + c[live]) if live.any() \
            else np.empty(0, np.int64)
        uniq, counts = np.unique(keys, return_counts=True)
        dup = uniq[counts > 1]
        for k in dup[:3]:
            rr, cc = int(k // (c.max() + 2)), int(k % (c.max() + 2))
            findings.append(Finding(
                "race", f"storage tile ({rr}, {cc}) written by "
                f"multiple live steps of one launch", device=d))
        if len(dup) > 3:
            findings.append(Finding(
                "race", f"... {len(dup)} colliding storage tiles "
                f"total", device=d))


def _check_bounds(plan, refs_per_device, per_device, model, findings):
    nr, nc = storage_grid(plan)
    hr, hc = neighbor_grid(plan)
    for d, (ids, bx, by, live) in enumerate(per_device):
        refs = refs_per_device[d]
        r, c = storage_tiles(plan, refs, ids)
        if r.min() < 0 or r.max() >= nr or c.min() < 0 or c.max() >= nc:
            findings.append(Finding(
                "bounds", f"storage index hull "
                f"rows [{r.min()}, {r.max()}] x cols "
                f"[{c.min()}, {c.max()}] exceeds the ({nr}, {nc}) "
                f"tile grid (some tile copy may go OOB)",
                device=d))
        if not model["neighbors"]:
            continue
        for j in range(len(NEIGHBOR_OFFSETS8)):
            r, c = neighbor_tiles(plan, refs, ids, j)
            if r.min() < 0 or r.max() >= hr or c.min() < 0 \
                    or c.max() >= hc:
                findings.append(Finding(
                    "bounds", f"neighbor {j} index hull rows "
                    f"[{r.min()}, {r.max()}] x cols "
                    f"[{c.min()}, {c.max()}] exceeds the ({hr}, {hc}) "
                    f"halo tile grid", device=d))


def _check_alias(plan, refs_per_device, per_device, model, findings):
    for read_model in model["alias_reads"]:
        if read_model == "none":
            continue
        for d, (ids, bx, by, live) in enumerate(per_device):
            refs = refs_per_device[d]
            r, c = storage_tiles(plan, refs, ids)
            writes = {}
            for s in np.nonzero(live)[0]:
                writes[(int(r[s]), int(c[s]))] = int(s)
            # "center" reads: step s reads its own write tile -- a
            # cross-step hazard is exactly a write-set collision and is
            # reported by the race check; nothing more to do here.
            if read_model != "center+neighbors":
                continue
            hit = None
            for j in range(len(NEIGHBOR_OFFSETS8)):
                nr_, nc_ = neighbor_tiles(plan, refs, ids, j)
                for s in np.nonzero(live)[0]:
                    t = writes.get((int(nr_[s]), int(nc_[s])))
                    if t is not None and t != int(s):
                        hit = (int(s), j, t)
                        break
                if hit:
                    break
            if hit:
                s, j, t = hit
                findings.append(Finding(
                    "alias", f"aliased input is read at neighbor {j} "
                    f"of step {s}, which is the write tile of step "
                    f"{t}: in-place aliasing makes this a "
                    f"read-after-write hazard within the launch",
                    device=d))


def _check_tables(plan, findings):
    dom = plan.sched_domain
    gx, gy = members_host(dom)
    n = dom.num_blocks
    if len(gx) != n:
        findings.append(Finding(
            "table", f"membership enumerates {len(gx)} blocks but "
            f"num_blocks = {n}"))
        return
    li = _np(dom.linear_index(gx, gy)).astype(np.int64)
    li = np.broadcast_to(li, gx.shape)
    if li.min() < 0 or li.max() >= n or len(np.unique(li)) != n:
        findings.append(Finding(
            "table", "linear_index over the member set is not a "
            "permutation of [0, num_blocks)"))
        return
    # expected coords table, placed via the *inverse* map
    exp = np.zeros((n, 2), np.int64)
    exp[li, 0] = gx
    exp[li, 1] = gy
    # for mma plans, verify the digit-basis matmul table -- the exact
    # decode the kernels consume
    lut = np.asarray(plan.mma_table_host() if plan.lowering == "mma"
                     else plan.lut_host())
    bad = np.nonzero((lut[:, _LUT_BX] != exp[:, 0])
                     | (lut[:, _LUT_BY] != exp[:, 1]))[0]
    for i in bad[:3]:
        findings.append(Finding(
            "table", f"LUT row {i} decodes to "
            f"({lut[i, _LUT_BX]}, {lut[i, _LUT_BY]}); linear_index "
            f"places ({exp[i, 0]}, {exp[i, 1]}) there"))
    if len(bad) > 3:
        findings.append(Finding(
            "table", f"... {len(bad)} corrupted LUT coordinate rows"))
    if plan.storage != "compact":
        return
    _check_compact_tables(plan, lut, exp, findings)


def _check_compact_tables(plan, lut, exp, findings):
    dom = plan.sched_domain
    n = dom.num_blocks
    if plan._tiling is not None:
        sx, sy = plan._tiling.tile_index(exp[:, 0], exp[:, 1])
    else:
        sx, sy = plan.layout.slot(exp[:, 0], exp[:, 1])
    sx = _np(sx).astype(np.int64)
    sy = _np(sy).astype(np.int64)
    bad = np.nonzero((lut[:, _LUT_SX] != sx)
                     | (lut[:, _LUT_SY] != sy))[0]
    for i in bad[:3]:
        findings.append(Finding(
            "table", f"LUT row {i}: packed slot "
            f"({lut[i, _LUT_SX]}, {lut[i, _LUT_SY]}) != lambda^-1 "
            f"slot ({sx[i]}, {sy[i]})"))
    if len(bad) > 3:
        findings.append(Finding(
            "table", f"... {len(bad)} corrupted slot rows"))
    nr, nc = storage_grid(plan) if not _is_sharded(plan) else \
        (lambda g: (g[1], g[0]))(plan._storage_grid())
    if len(np.unique(sy * nc + sx)) != n or sx.min() < 0 \
            or sx.max() >= nc or sy.min() < 0 or sy.max() >= nr:
        findings.append(Finding(
            "table", "lambda^-1 slots are not an injection into the "
            "storage grid"))
        return
    # semantic neighbour check: every valid neighbour slot must invert
    # (via the slot -> coords table) to exactly the embedded neighbour
    slot2coord = np.full((nr, nc, 2), -1, np.int64)
    slot2coord[sy, sx, 0] = exp[:, 0]
    slot2coord[sy, sx, 1] = exp[:, 1]
    nbx, nby = dom.bounding_box
    nbrs = lut[:, _LUT_NBR:].reshape(n, 8, 3).astype(np.int64)
    for j, (dx, dy) in enumerate(NEIGHBOR_OFFSETS8):
        ex = exp[:, 0] + dx
        ey = exp[:, 1] + dy
        inb = (ex >= 0) & (ex < nbx) & (ey >= 0) & (ey < nby)
        mem = np.zeros(n, bool)
        if inb.any():
            mem[inb] = np.broadcast_to(
                _np(dom.contains(ex[inb], ey[inb])),
                ex[inb].shape).astype(bool)
        ok = nbrs[:, j, 2] == 1
        bad = np.nonzero(ok != mem)[0]
        for i in bad[:2]:
            findings.append(Finding(
                "table", f"neighbor table row {i} offset {j}: "
                f"valid={bool(ok[i])} but membership says "
                f"{bool(mem[i])}"))
        if len(bad) > 2:
            findings.append(Finding(
                "table", f"... {len(bad)} wrong neighbour-validity "
                f"entries at offset {j}"))
        nsx, nsy = nbrs[:, j, 0], nbrs[:, j, 1]
        if nsx.min() < 0 or nsx.max() >= nc or nsy.min() < 0 \
                or nsy.max() >= nr:
            findings.append(Finding(
                "table", f"neighbour slots at offset {j} leave the "
                f"storage grid (clamped reads would alias wrong "
                f"tiles)"))
            continue
        sel = np.nonzero(ok & mem)[0]
        got = slot2coord[nsy[sel], nsx[sel]]
        bad = sel[np.nonzero((got[:, 0] != ex[sel])
                             | (got[:, 1] != ey[sel]))[0]]
        for i in bad[:2]:
            findings.append(Finding(
                "table", f"neighbor slot of row {i} offset {j} "
                f"resolves to block "
                f"{tuple(slot2coord[nbrs[i, j, 1], nbrs[i, j, 0]])}, "
                f"expected ({exp[i, 0] + dx}, {exp[i, 1] + dy})"))
        if len(bad) > 2:
            findings.append(Finding(
                "table", f"... {len(bad)} mis-resolved neighbour "
                f"slots at offset {j}"))


# -- sharded table checks ----------------------------------------------------

def _rederived_partition(plan):
    """Independent (lo, count) per device from the partition rule."""
    D = plan.num_shards
    N = plan.sched_domain.num_blocks
    if plan.partition == "storage-rows":
        lo = np.minimum(np.arange(D) * plan.rpd * plan.ncols, N)
        return lo, np.minimum(N - lo, plan.rpd * plan.ncols).clip(min=0)
    if plan.partition == "rows":
        nby = plan.sched_domain.bounding_box[1]
        by = np.sort(members_host(plan.sched_domain)[1])
        row_lo = np.minimum(np.arange(D + 1) * plan.rbd, nby)
        lo = np.searchsorted(by, row_lo, side="left")
        return lo[:-1], np.diff(lo)
    per = -(-N // D)
    lo = np.minimum(np.arange(D) * per, N)
    return lo, np.minimum(N - lo, per).clip(min=0)


def _rederive_halo(plan):
    """(ghost classes, interior steps, boundary steps, column spans)
    per device, re-derived from the (already verified) neighbour
    tables.  Spans map (ghost row, class) -> the half-open slot-column
    span of that row's readers."""
    if plan._tiling is not None:
        own = plan._tiling.tiles_host()
        nbrs = plan._tiling.neighbor_tiles_host()
    else:
        own = plan.layout.slots_host()
        nbrs = plan.layout.neighbor_slots_host()
    D, rpd = plan.num_shards, plan.rpd
    strips = plan.tile_map() is None
    ghosts, ints, bnds, spans = [], [], [], []
    for d in range(D):
        lo, hi = d * rpd, min((d + 1) * rpd, plan.nrows)
        sel = (own[:, 1] >= lo) & (own[:, 1] < hi)
        nb, mine = nbrs[sel], own[sel]
        cls: Dict[int, set] = {}
        span: Dict[tuple, tuple] = {}
        for j, (dx, dy) in enumerate(NEIGHBOR_OFFSETS8):
            rem = (nb[:, j, 2] == 1) \
                & ((nb[:, j, 1] < lo) | (nb[:, j, 1] >= hi))
            gr, gc = nb[:, j, 1][rem], nb[:, j, 0][rem]
            c = "top" if strips and dy == 1 else \
                "bot" if strips and dy == -1 else "full"
            for g in np.unique(gr):
                cols = gc[gr == g]
                cls.setdefault(int(g), set()).add(c)
                key = (int(g), c)
                clo, chi = int(cols.min()), int(cols.max()) + 1
                if key in span:
                    plo, phi = span[key]
                    span[key] = (min(plo, clo), max(phi, chi))
                else:
                    span[key] = (clo, chi)
        for g, s in cls.items():
            if "full" in s:
                merged = [span.pop((g, c)) for c in s if (g, c) in span]
                cls[g] = {"full"}
                span[(g, "full")] = (min(x for x, _ in merged),
                                     max(y for _, y in merged))
        ghosts.append(cls)
        spans.append(span)
        remote = (nb[..., 2] == 1) \
            & ((nb[..., 1] < lo) | (nb[..., 1] >= hi))
        t_ids = (mine[:, 1] - lo) * plan.ncols + mine[:, 0]
        bnd = remote.any(axis=1)
        ints.append(sorted(int(t) for t in t_ids[~bnd]))
        bnds.append(sorted(int(t) for t in t_ids[bnd]))
    return ghosts, ints, bnds, spans


def _check_shard_tables(plan, findings):
    D = plan.num_shards
    tbl = np.asarray(plan.shard_table_host())
    lo, count = _rederived_partition(plan)
    exp_lo = np.arange(D) * plan.rpd \
        if plan.partition == "storage-rows" else lo
    if not np.array_equal(tbl[:, SHARD_LO], exp_lo):
        findings.append(Finding(
            "table", f"shard table lo column {tbl[:, SHARD_LO]} != "
            f"re-derived {exp_lo}"))
    if not np.array_equal(tbl[:, SHARD_COUNT], count):
        findings.append(Finding(
            "table", f"shard table count column "
            f"{tbl[:, SHARD_COUNT]} != re-derived {count}"))
    if plan.partition != "storage-rows":
        return
    ghosts, ints, bnds, spans = _rederive_halo(plan)
    halo = plan.halo
    rpd = plan.rpd
    with_halo = halo is not None and halo.int_steps is not None
    if not with_halo and any(g for g in ghosts):
        # write/sum plans skip the halo: nothing more to check
        ghosts = [dict() for _ in range(D)]
    h_max = max((len(g) for g in ghosts), default=0)
    dump = rpd + h_max
    for d in range(D):
        gmap = tbl[d, SHARD_GMAP:]
        exp = np.full(plan.nrows_pad, dump, np.int64)
        for i in range(rpd):
            if d * rpd + i < plan.nrows_pad:
                exp[d * rpd + i] = i
        for p, g in enumerate(sorted(ghosts[d])):
            exp[g] = rpd + p
        if not np.array_equal(gmap, exp):
            bad = np.nonzero(gmap != exp)[0]
            findings.append(Finding(
                "table", f"ghost map rows {bad[:5].tolist()} disagree "
                f"with the re-derived map (got "
                f"{gmap[bad[:5]].tolist()}, expected "
                f"{exp[bad[:5]].tolist()})", device=d))
    if with_halo:
        _check_halo_rounds(plan, ghosts, spans, findings)
        _check_phase_tables(plan, ints, bnds, findings)
    if plan._table_backed:
        _check_sharded_lut(plan, findings)


def _check_halo_rounds(plan, ghosts, spans, findings):
    """Simulate the ppermute rounds and check every ghost row's strip
    requirement is delivered to its slot exactly, with a column window
    that covers its readers' span."""
    halo, D, rpd = plan.halo, plan.num_shards, plan.rpd
    order = [sorted(g) for g in ghosts]
    delivered: List[Dict[int, set]] = [dict() for _ in range(D)]
    for delta, cls, send, recv, scol, rcol, wc in halo.rounds:
        m = send.shape[1]
        for d in range(D):
            src = (d - delta) % D
            for i in range(m):
                slot = int(recv[d, i])
                if slot == halo.h_max:
                    continue  # padding -> dump row
                g = int(send[src, i]) + src * rpd
                if slot >= len(order[d]) or order[d][slot] != g:
                    findings.append(Finding(
                        "table", f"halo round (delta={delta}, "
                        f"{cls}): ghost slot {slot} receives global "
                        f"row {g}, expected "
                        f"{order[d][slot] if slot < len(order[d]) else 'dump'}",
                        device=d))
                    continue
                c0 = int(rcol[d, i])
                if int(scol[src, i]) != c0:
                    findings.append(Finding(
                        "table", f"halo round (delta={delta}, {cls}):"
                        f" ghost row {g} gathered at source column "
                        f"{int(scol[src, i])} but scattered at "
                        f"{c0}", device=d))
                lo_, hi_ = spans[d].get((g, cls), (0, 0))
                if c0 < 0 or c0 + wc > plan.ncols \
                        or not (c0 <= lo_ and hi_ <= c0 + wc):
                    findings.append(Finding(
                        "table", f"halo round (delta={delta}, {cls}):"
                        f" ghost row {g} window [{c0}, {c0 + wc}) "
                        f"misses its reader span [{lo_}, {hi_}) or "
                        f"exceeds [0, {plan.ncols})", device=d))
                delivered[d].setdefault(g, set()).add(cls)
    for d in range(D):
        for g, need in ghosts[d].items():
            got = delivered[d].get(g, set())
            if not need <= got:
                findings.append(Finding(
                    "table", f"ghost row {g} needs strips "
                    f"{sorted(need)} but the rounds deliver "
                    f"{sorted(got)}", device=d))


def _check_phase_tables(plan, ints, bnds, findings):
    tabs = plan.phase_tables_host()
    halo = plan.halo
    for d in range(plan.num_shards):
        if halo.int_steps[d] != ints[d] or halo.bnd_steps[d] != bnds[d]:
            findings.append(Finding(
                "table", "interior/boundary step partition disagrees "
                "with the re-derived remote-neighbour classification",
                device=d))
            continue
        owned = sorted(ints[d] + bnds[d])
        count = int(_rederived_partition(plan)[1][d])
        if owned != list(range(count)):
            findings.append(Finding(
                "table", f"phase step lists do not partition the "
                f"{count} owned steps", device=d))
    if tabs is None:
        return
    it, bt = tabs
    for d in range(plan.num_shards):
        for name, tab, ref in (("interior", it, ints),
                               ("boundary", bt, bnds)):
            k = int(tab[d, 0])
            if k != len(ref[d]) \
                    or tab[d, 1:1 + k].tolist() != ref[d]:
                findings.append(Finding(
                    "table", f"{name} phase table row disagrees with "
                    f"the re-derived step list", device=d))


def _check_sharded_lut(plan, findings):
    """Each device's decode-table chunk must decode its slab row-major:
    chunk row t (t < count) is the member block whose packed slot is
    (t % ncols, lo + t // ncols).  Applies to every table-backed
    lowering (prefetch_lut, mma)."""
    D = plan.num_shards
    if plan.partition != "storage-rows":
        return
    per = plan.rpd * plan.ncols
    lut = plan.lut_sharded_host()
    if lut is None:
        lut = plan.mma_table_sharded_host()
    lut = np.asarray(lut)
    if plan._tiling is not None:
        slot = plan._tiling.tile_index
    else:
        slot = plan.layout.slot
    tbl = np.asarray(plan.shard_table_host())
    _, count = _rederived_partition(plan)
    for d in range(D):
        chunk = lut[d * per:(d + 1) * per]
        c = int(count[d])
        if c == 0:
            continue
        t = np.arange(c)
        sx, sy = slot(chunk[:c, _LUT_BX].astype(np.int64),
                      chunk[:c, _LUT_BY].astype(np.int64))
        sx = _np(sx).astype(np.int64)
        sy = _np(sy).astype(np.int64)
        row0 = int(tbl[d, SHARD_LO])
        bad = np.nonzero((sx != t % plan.ncols)
                         | (sy != row0 + t // plan.ncols))[0]
        for i in bad[:3]:
            findings.append(Finding(
                "table", f"sharded LUT chunk row {i} decodes to slot "
                f"({sx[i]}, {sy[i]}), expected "
                f"({i % plan.ncols}, {row0 + i // plan.ncols})",
                device=d))
        if len(bad) > 3:
            findings.append(Finding(
                "table", f"... {len(bad)} misplaced sharded LUT rows",
                device=d))


def _check_phase_views(plan, findings):
    """Interior + boundary launches together must cover each owned step
    exactly once, with decodes equal to the base launch's."""
    if plan.phase_tables_host() is None:
        return
    views = [plan.phase_view("interior"), plan.phase_view("boundary")]
    for d in range(plan.num_shards):
        base_refs = host_prefetch_refs(plan, d)
        ids, bx, by, live = decode_steps(plan, base_refs)
        base = {}
        for s in np.nonzero(live)[0]:
            base[int(ids[-1][s])] = (int(bx[s]), int(by[s]))
        covered: Dict[int, int] = {}
        for view in views:
            refs = host_prefetch_refs(view, d)
            vids, vbx, vby, vlive = decode_steps(view, refs)
            ptab = refs[-1]
            for s in np.nonzero(vlive)[0]:
                t = int(ptab[1 + int(vids[-1][s])])
                covered[t] = covered.get(t, 0) + 1
                if base.get(t) != (int(vbx[s]), int(vby[s])):
                    findings.append(Finding(
                        "coverage", f"phase {view.phase} step {s} "
                        f"decodes {(int(vbx[s]), int(vby[s]))} but "
                        f"base step {t} decodes {base.get(t)}",
                        device=d))
        if covered != {t: 1 for t in base}:
            findings.append(Finding(
                "coverage", "interior+boundary phases do not cover "
                "each owned step exactly once", device=d))


def _check_flash_hulls(plan, findings):
    """Flash q/k window hulls.  The XLA-level ``triangular`` flash
    schedule (:mod:`repro.models.attention`) walks key blocks
    ``start..end`` of each query row, so correctness needs (a) every
    block row of the domain to be a *contiguous* span -- a hole would
    be visited and attended to -- and (b) the host ``row_extents``
    table it consumes to equal the hull re-derived from membership and
    stay inside the block grid (an out-of-range extent would slice the
    wrong keys)."""
    dom = plan.sched_domain
    gx, gy = members_host(dom)
    nbx, nby = dom.bounding_box
    exp = np.zeros((nby, 2), np.int64)
    exp[:, 1] = -1
    for row in range(nby):
        xs = np.unique(gx[gy == row])
        if not len(xs):
            continue
        exp[row, 0], exp[row, 1] = xs.min(), xs.max()
        if len(xs) != exp[row, 1] - exp[row, 0] + 1:
            findings.append(Finding(
                "hull", f"block row {row} has holes: the flash key "
                f"loop over [{exp[row, 0]}, {exp[row, 1]}] would "
                f"attend to non-member tiles"))
    ext = np.asarray(plan.row_extents()).astype(np.int64)
    if ext.shape != (nby, 2):
        findings.append(Finding(
            "hull", f"row_extents has shape {ext.shape}, expected "
            f"{(nby, 2)}"))
        return
    occ = exp[:, 1] >= exp[:, 0]
    if np.any((ext[occ, 0] < 0) | (ext[occ, 1] >= nbx)):
        findings.append(Finding(
            "hull", f"row_extents leaves the {nbx}-wide block grid"))
    bad = np.nonzero((ext[:, 0] != exp[:, 0])
                     | (ext[:, 1] != exp[:, 1]))[0]
    for row in bad[:3]:
        findings.append(Finding(
            "hull", f"row_extents row {row} = "
            f"[{ext[row, 0]}, {ext[row, 1]}] but the membership "
            f"hull is [{exp[row, 0]}, {exp[row, 1]}]"))
    if len(bad) > 3:
        findings.append(Finding(
            "hull", f"... {len(bad)} wrong row_extents rows"))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def verify_plan(plan: GridPlan, *, kernel: str = "generic",
                checks: Optional[Sequence[str]] = None) -> Report:
    """Run every applicable static check for ``plan`` under the named
    kernel access model (see :data:`ACCESS_MODELS`); returns a
    :class:`Report` (``.ok`` / ``.findings``)."""
    import jax

    # host-side static analysis even when invoked from inside a kernel's
    # jit trace (the verify= debug flag): the mma lowering's decode
    # chains are jnp, and staging them would make every re-derived
    # value a tracer.
    with jax.ensure_compile_time_eval():
        return _verify_plan_host(plan, kernel, checks)


def _verify_plan_host(plan, kernel, checks):
    model = ACCESS_MODELS[kernel]
    all_checks = ("coverage", "race", "table", "bounds", "alias", "hull")
    selected = tuple(checks) if checks is not None else all_checks
    findings: List[Finding] = []
    D = num_devices(plan)
    refs_per_device = [host_prefetch_refs(plan, d) for d in range(D)]
    per_device = [decode_steps(plan, refs_per_device[d])
                  for d in range(D)]

    if "coverage" in selected and _phase(plan) is None:
        _check_coverage(plan, per_device, findings)
    if "table" in selected:
        _check_tables(plan, findings)
        if _is_sharded(plan) and _phase(plan) is None:
            _check_shard_tables(plan, findings)
    if model["storage"]:
        if "race" in selected and model["race"]:
            _check_race(plan, refs_per_device, per_device, findings)
        if "bounds" in selected:
            _check_bounds(plan, refs_per_device, per_device, model,
                          findings)
        if "alias" in selected and model["alias_reads"]:
            _check_alias(plan, refs_per_device, per_device, model,
                         findings)
    if "hull" in selected and model.get("hulls"):
        _check_flash_hulls(plan, findings)
    if "coverage" in selected and _is_sharded(plan) \
            and _phase(plan) is None \
            and plan.partition == "storage-rows" \
            and plan.halo is not None \
            and plan.halo.int_steps is not None \
            and plan.lowering != "bounding":
        _check_phase_views(plan, findings)
    return Report(plan=plan_signature(plan), checks=selected,
                  findings=findings)


def verify_or_raise(plan: GridPlan, *, kernel: str = "generic",
                    checks: Optional[Sequence[str]] = None) -> Report:
    """``verify_plan`` + raise :class:`PlanVerificationError` on any
    finding -- the ``verify=`` debug-flag entry point of the kernels."""
    return verify_plan(plan, kernel=kernel,
                       checks=checks).raise_on_findings()


# ---------------------------------------------------------------------------
# paged KV page tables (the serving scheduler's host invariants)
# ---------------------------------------------------------------------------

def verify_page_table(table, seq_lens, *, page_size: int,
                      num_pages: int, free_pages=(),
                      null_page: int = 0) -> Report:
    """Re-derive the page-table invariants of the paged KV pool from
    first principles and report violations (the host-side analogue of
    the plan LUT checks -- the table *is* a decode LUT pointed at
    physical memory).

    table:      (num_slots, max_pages) i32; seq_lens: per-slot live
    token counts (0 = inactive).  Each slot's *active extent* is its
    first ``ceil(len / page_size)`` entries.  Checks:

    * **bounds** -- every entry in [0, num_pages);
    * **null-in-extent** -- no active extent maps the null page (a
      reader would consume trash-page garbage);
    * **double-map** -- no physical page owned by two active extents
      (a write in one request would corrupt another's KV);
    * **stale-free** -- no active extent maps a page on the free list
      (the allocator would hand it to the next admission: a
      use-after-free);
    * **tail-null** -- entries past the active extent are the null
      page (a stale mapping there is a freed-page leak waiting for a
      ``seq_pos`` bug to read it).
    """
    table = np.asarray(table)
    findings: List[Finding] = []
    if table.ndim != 2:
        raise ValueError(f"page table must be 2-D, got {table.shape}")
    if len(seq_lens) != table.shape[0]:
        raise ValueError(f"{len(seq_lens)} seq_lens for "
                         f"{table.shape[0]} slots")
    free = set(int(p) for p in free_pages)
    bad = (table < 0) | (table >= num_pages)
    if bad.any():
        s, j = map(int, np.argwhere(bad)[0])
        findings.append(Finding(
            "bounds", f"slot {s} entry {j} = {int(table[s, j])} outside "
            f"[0, {num_pages})"))
    owner: Dict[int, int] = {}
    for s, n in enumerate(seq_lens):
        ext = -(-int(n) // page_size)
        for j in range(ext):
            p = int(table[s, j])
            if p == null_page:
                findings.append(Finding(
                    "null-in-extent",
                    f"slot {s} ({n} tokens) maps the null page at "
                    f"entry {j}"))
                continue
            if p in owner and owner[p] != s:
                findings.append(Finding(
                    "double-map",
                    f"page {p} mapped by slots {owner[p]} and {s}"))
            owner[p] = s
            if p in free:
                findings.append(Finding(
                    "stale-free",
                    f"slot {s} entry {j} maps freed page {p}"))
        tail = table[s, ext:]
        if (tail != null_page).any():
            j = ext + int(np.argmax(tail != null_page))
            findings.append(Finding(
                "tail-null",
                f"slot {s} ({n} tokens, extent {ext}) still maps page "
                f"{int(table[s, j])} at entry {j}"))
    plan_sig = {"kind": "page-table", "slots": int(table.shape[0]),
                "max_pages": int(table.shape[1]),
                "page_size": int(page_size),
                "num_pages": int(num_pages)}
    return Report(plan=plan_sig,
                  checks=("bounds", "null-in-extent", "double-map",
                          "stale-free", "tail-null"),
                  findings=findings).raise_on_findings()
