"""Mesh-aware block-space execution: ShardedPlan partitions a
BlockDomain across one axis of a ``jax.sharding.Mesh`` and lowers each
device's sub-domain through the existing GridPlan paths (closed_form /
prefetch_lut / bounding) inside ``shard_map``.

Partitions
----------

``"storage-rows"`` (compact storage)
    The packed orthotope of :class:`~repro.core.compact.CompactLayout`
    is split into D contiguous *slot-row* slabs (supertile rows under
    ``coarsen``, via the existing :class:`SuperTiling` geometry), padded
    to a common height.  Each device holds only its slab -- per-device
    memory is O(n^H / D) + halo -- and enumerates its slots row-major:
    the closed-form decode is ``lambda(w_x, w_y)`` evaluated directly on
    the orthotope coordinate (``FractalSpec.lambda_map``), i.e. the
    paper's map re-rooted at the device's first packed row.  Because the
    fractal orthotope is dense (Lemma 2: num_slots == num_blocks), equal
    row slabs are an exactly balanced work partition.

``"linear"`` (embedded storage)
    The canonical lambda-order enumeration [0, num_blocks) is split into
    D contiguous ranges -- sharding the paper's *parallel space* itself.
    State arrays stay replicated (they are already the dense O(n^2)
    layout); each device computes its range and the driver combines with
    a disjoint-ownership-mask ``psum`` (exact: every cell has exactly
    one nonzero contributor).

``"rows"`` (attention: the query-block axis)
    Query-block rows are split into D contiguous bands; the domains'
    canonical enumerations are row-major in the query block, so each
    band is a contiguous linear range and the closed-form decode is the
    parent decode at a per-device offset.  Q and O shard along the
    sequence axis; K/V stay replicated.

``"zigzag"`` (attention: balanced causal bands)
    Contiguous bands are pathological for *causal* attention: row ``j``
    of a triangular domain holds ``j + 1`` key blocks, so the last
    device does ~``(2D - 1)/1`` times the work of the first.  The
    zig-zag (snake) assignment gives device ``d`` rows ``{j : min(r,
    2D-1-r) == d}`` with ``r = j mod 2D`` -- pairing light row ``k*2D +
    d`` with heavy row ``k*2D + (2D-1-d)`` so every pair contributes
    ``(2k)*2D + 2D + 1`` blocks *independent of d*: with ``nby % 2D ==
    0`` (enforced) the split is exactly balanced.  The owned rows are
    scattered, so the per-device enumeration is table-backed
    (prefetch_lut / mma chunks carry global coords; ``bounding``
    reconstructs the global row from the device id in the shard table);
    the local row of global ``j`` is the closed form ``2*(j // 2D) + (r
    >= D)``, used by ``_place_coords`` to address the device's Q/O
    band.  Drivers permute Q block rows into the device-concatenated
    snake order before shard_map and inverse-permute O after
    (:func:`zigzag_row_order`).

Per-device parameters inside SPMD
---------------------------------

``shard_map`` traces one program for all devices, so anything
device-dependent must arrive through *sharded operands*.  Every sharded
lowering therefore carries one extra scalar-prefetch operand, the
**shard table** -- ``[lo_or_row_lo, count, ...]`` plus, under compact
storage, the ghost-row map -- and ``prefetch_lut`` additionally ships
its (per-device, padded) decode LUT.  Validity of a grid step
(padding, uneven splits, ownership under ``bounding``) is folded into
``BlockCoords.valid``, which every kernel already honours.

Halo exchange (compact CA)
--------------------------

A slab's blocks have embedded neighbours whose lambda^-1-resolved slots
may live in other devices' slabs -- and orthotope row distance is not
embedded distance, so the ghost rows of a slab are a *scattered* set of
remote rows.  :class:`HaloPlan` resolves them host-side from the
layout's neighbour tables, and exchanges exactly those rows between
launches with one ``jax.lax.ppermute`` per active device offset; the
kernel then reads ``[local slab ++ ghost rows ++ dump row]`` through the
shard table's ghost map.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import memo
from .compact import NEIGHBOR_OFFSETS8
from .domain import BlockDomain
from .plan import _LUT_NBR, GridPlan

PARTITIONS = ("linear", "rows", "storage-rows", "zigzag")

#: shard-table column layout (i32): [0] the device's linear offset
#: (linear/rows) or first owned storage row (storage-rows); [1] the
#: number of valid grid steps / owned blocks; [2] the first owned
#: query-block row ("rows") or the device index ("zigzag") -- then, for
#: "storage-rows", the ghost map (global storage row -> row of the
#: device's extended local array).
SHARD_LO = 0
SHARD_COUNT = 1
SHARD_ROWLO = 2
SHARD_DEV = 2
SHARD_GMAP = 2


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _widen(spans: dict, key, lo: int, hi: int) -> None:
    """Grow ``spans[key]`` to cover the half-open column span
    [lo, hi)."""
    if key in spans:
        plo, phi = spans[key]
        spans[key] = (min(plo, lo), max(phi, hi))
    else:
        spans[key] = (lo, hi)


class HaloPlan:
    """Host-resolved ghost-row exchange for a storage-row partition.

    For each device: which global storage rows (supertile rows under
    coarsening) its halo needs (``ghost_rows``), and the padded
    send/recv index tables the ``ppermute`` rounds use.  ``h_max``
    ghost rows (+1 dump row for padding traffic and never-needed rows)
    bound the halo memory.

    Rounds are keyed (device offset ``delta``, strip class): the
    trapezoid update reads a ``dy = +1`` neighbour's *top* ``h`` cell
    rows and a ``dy = -1`` neighbour's *bottom* ``h`` rows (``h`` =
    the fuse depth), so a ghost row whose readers all sit on one side
    ships only that strip instead of its full ``row_unit`` height.
    ``dy = 0`` readers (and packed supertiles, whose cell rows are not
    embedded-ordered -- ``plan.tile_map() is not None``) force the
    full row.  Orthogonally, each entry ships only the *occupied
    column window*: the span of slot columns its receiver's readers
    actually resolve (``col_span``), widened to the round's max width
    ``wcols`` and clamped into ``[0, ncols)`` so every payload in a
    round has one static shape.  Unshipped strip/column cells stay
    zero and are never read by a valid step.  The partition of each
    device's steps into *interior*
    (all 8 neighbour rows local) and *boundary* (any ghost neighbour)
    -- ``int_steps`` / ``bnd_steps`` -- is what lets a driver overlap
    the exchange with interior compute (:meth:`ShardedPlan.phase_view`).
    """

    def __init__(self, plan: "ShardedPlan", with_halo: bool):
        D, rpd, nrows = plan.num_shards, plan.rpd, plan.nrows
        self.ghost_rows = [[] for _ in range(D)]
        self.row_class = [dict() for _ in range(D)]
        self.col_span = [dict() for _ in range(D)]  # (g, cls) -> (lo, hi)
        self.int_steps = None
        self.bnd_steps = None
        if with_halo:
            if plan._tiling is not None:
                own = plan._tiling.tiles_host()
                nbrs = plan._tiling.neighbor_tiles_host()
            else:
                own = plan.layout.slots_host()
                nbrs = plan.layout.neighbor_slots_host()
            rows = own[:, 1]
            strips = plan.tile_map() is None
            self.int_steps = [[] for _ in range(D)]
            self.bnd_steps = [[] for _ in range(D)]
            for d in range(D):
                lo, hi = d * rpd, min((d + 1) * rpd, nrows)
                sel = (rows >= lo) & (rows < hi)
                nb, mine = nbrs[sel], own[sel]
                cls = self.row_class[d]
                span = self.col_span[d]
                for j, (dx, dy) in enumerate(NEIGHBOR_OFFSETS8):
                    rem = (nb[:, j, 2] == 1) \
                        & ((nb[:, j, 1] < lo) | (nb[:, j, 1] >= hi))
                    gr, gc = nb[:, j, 1][rem], nb[:, j, 0][rem]
                    c = "top" if strips and dy == 1 else \
                        "bot" if strips and dy == -1 else "full"
                    for g in np.unique(gr):
                        cols = gc[gr == g]
                        cls.setdefault(int(g), set()).add(c)
                        _widen(span, (int(g), c),
                               int(cols.min()), int(cols.max()) + 1)
                for g, s in cls.items():
                    if "full" in s:
                        merged = [span.pop((g, c)) for c in s
                                  if (g, c) in span]
                        cls[g] = {"full"}
                        span[(g, "full")] = (
                            min(x for x, _ in merged),
                            max(y for _, y in merged))
                self.ghost_rows[d] = sorted(cls)
                remote = (nb[..., 2] == 1) \
                    & ((nb[..., 1] < lo) | (nb[..., 1] >= hi))
                t_ids = (mine[:, 1] - lo) * plan.ncols + mine[:, 0]
                bnd = remote.any(axis=1)
                self.int_steps[d] = sorted(int(t) for t in t_ids[~bnd])
                self.bnd_steps[d] = sorted(int(t) for t in t_ids[bnd])
        self.h_max = max((len(g) for g in self.ghost_rows), default=0)
        # ghost map: global row -> row of [slab ++ ghosts ++ dump]
        dump = rpd + self.h_max
        gmap = np.full((D, plan.nrows_pad), dump, np.int32)
        for d in range(D):
            lo = d * rpd
            for i in range(rpd):
                if lo + i < plan.nrows_pad:
                    gmap[d, lo + i] = i
            for p, g in enumerate(self.ghost_rows[d]):
                gmap[d, g] = rpd + p
        self.ghost_map = gmap
        # ppermute rounds: one per (device offset, strip class) with
        # any traffic
        self.rounds = []   # [(delta, cls, send (D, m), recv (D, m),
        #                     scol (D, m), rcol (D, m), wcols)]
        for delta in range(1, D):
            for cls in ("full", "top", "bot"):
                needs = [[g for g in self.ghost_rows[d]
                          if g // rpd == (d - delta) % D
                          and cls in self.row_class[d][g]]
                         for d in range(D)]
                m = max(len(x) for x in needs)
                if m == 0:
                    continue
                wc = max(hi_ - lo_ for d in range(D) for g in needs[d]
                         for lo_, hi_ in (self.col_span[d][(g, cls)],))
                send = np.zeros((D, m), np.int32)
                recv = np.full((D, m), self.h_max, np.int32)  # pad -> dump
                scol = np.zeros((D, m), np.int32)
                rcol = np.zeros((D, m), np.int32)
                for d in range(D):
                    for i, g in enumerate(needs[(d + delta) % D]):
                        send[d, i] = g - d * rpd  # local row at source
                        sp = self.col_span[(d + delta) % D][(g, cls)]
                        scol[d, i] = min(sp[0], plan.ncols - wc)
                    for i, g in enumerate(needs[d]):
                        recv[d, i] = self.ghost_rows[d].index(g)
                        sp = self.col_span[d][(g, cls)]
                        rcol[d, i] = min(sp[0], plan.ncols - wc)
                self.rounds.append(
                    (delta, cls, send, recv, scol, rcol, wc))

    def send_recv_host(self):
        """((send, recv, scol, rcol), ...) host tables, one 4-tuple
        per round; drivers pass them into shard_map sharded along the
        mesh axis.  ``scol``/``rcol`` are the clamped first slot
        column of each entry's shipped window (source / receiver side;
        equal by construction -- both resolve the receiver's span)."""
        return tuple((s, r, sc, rc)
                     for _, _, s, r, sc, rc, _ in self.rounds)

    def _strip(self, cls: str, RU: int, h: int):
        """(row offset, height) of one class's strip within a row."""
        if cls == "top":
            return 0, h
        if cls == "bot":
            return RU - h, h
        return 0, RU

    def exchange(self, plan: "ShardedPlan", local: jnp.ndarray,
                 send_recv, h: Optional[int] = None) -> jnp.ndarray:
        """Inside shard_map: run every ppermute round and return the
        ghost block ((h_max + 1), RU, W) = exchanged ghost rows ++ a
        zero-init dump row.  ``h`` is the strip height in cells (the
        launch fuse depth); ``None`` ships full rows.  Each entry
        ships only its ``wcols``-slot-column window (gathered at the
        sender's ``scol``, scattered at the receiver's ``rcol``); the
        rest of the ghost row stays zero.  Independent of the local
        compute, so a driver can launch interior work while the
        collective is in flight and :meth:`cat` afterwards."""
        rpd, RU = plan.rpd, plan.row_unit
        h = RU if h is None else min(int(h), RU)
        W = local.shape[-1]
        tw = W // plan.ncols  # cell columns per slot column
        rows = local.reshape(rpd, RU, W)
        ghost = jnp.zeros((self.h_max + 1, RU, W), local.dtype)
        D = plan.num_shards
        for (delta, cls, *_, wc), (send, recv, scol, rcol) in zip(
                self.rounds, send_recv):
            off, nr = self._strip(cls, RU, h)
            base = rows[send.reshape(-1), off:off + nr]  # (m, nr, W)
            cidx = (scol.reshape(-1)[:, None] * tw
                    + jnp.arange(wc * tw))               # (m, wc*tw)
            payload = jnp.take_along_axis(base, cidx[:, None, :],
                                          axis=2)
            got = jax.lax.ppermute(
                payload, plan.axis,
                [(s, (s + delta) % D) for s in range(D)])
            ri = recv.reshape(-1)
            rr = off + jnp.arange(nr)
            cc = rcol.reshape(-1)[:, None] * tw + jnp.arange(wc * tw)
            ghost = ghost.at[ri[:, None, None], rr[None, :, None],
                             cc[:, None, :]].set(got)
        return ghost

    def cat(self, plan: "ShardedPlan", local: jnp.ndarray,
            ghost: jnp.ndarray) -> jnp.ndarray:
        """local slab (rpd*RU, W) ++ ghost block -> extended array
        ((rpd + h_max + 1)*RU, W) the kernels address via the shard
        table's ghost map."""
        rpd, RU = plan.rpd, plan.row_unit
        W = local.shape[-1]
        rows = local.reshape(rpd, RU, W)
        return jnp.concatenate([rows, ghost], axis=0).reshape(
            (rpd + self.h_max + 1) * RU, W)

    def extend(self, plan: "ShardedPlan", local: jnp.ndarray,
               send_recv, h: Optional[int] = None) -> jnp.ndarray:
        """exchange + cat: the synchronous (non-overlapped) path."""
        return self.cat(plan, local, self.exchange(plan, local,
                                                   send_recv, h))

    def bytes_exchanged(self, plan: "ShardedPlan", block: int,
                        h: Optional[int] = None,
                        itemsize: int = 4) -> dict:
        """Payload bytes one exchange moves across the whole mesh:
        ``trimmed`` (what :meth:`exchange` ships -- strip height ``h``
        x the per-round occupied column window, padding included) vs
        ``strips`` (strip-trimmed but full-width rows) vs
        ``full_rows`` (the pre-trim scheme: every ghost row at full
        row_unit height and width)."""
        plan.bind_block(block)
        RU = plan.row_unit
        tw = plan.supertile_shape((block, block))[1]
        W = plan.ncols * tw
        h = RU if h is None else min(int(h), RU)
        D, rpd = plan.num_shards, plan.rpd
        trimmed = sum(D * s.shape[1] * self._strip(cls, RU, h)[1]
                      * wc * tw * itemsize
                      for _, cls, s, _, _, _, wc in self.rounds)
        strips = sum(D * s.shape[1] * self._strip(cls, RU, h)[1] * W
                     * itemsize for _, cls, s, *_ in self.rounds)
        full = 0
        for delta in range(1, D):
            m = max(len([g for g in self.ghost_rows[d]
                         if g // rpd == (d - delta) % D])
                    for d in range(D))
            full += D * m * RU * W * itemsize
        return {"trimmed": trimmed, "strips": strips,
                "full_rows": full}


class ShardedPlan(GridPlan):
    """A GridPlan whose grid is one device's share of the domain.

    Parameters beyond :class:`GridPlan`:

    mesh, axis:  the jax Mesh and the name of the axis to shard over.
    partition:   "storage-rows" | "linear" | "rows" (default: by
                 storage -- compact shards its packed rows, embedded
                 shards the canonical enumeration).
    halo:        build the ghost-row exchange plan (CA stencils under
                 compact storage; write/sum leave it off).

    The plan's specs address *local* arrays: under "storage-rows" the
    device's padded slab (inputs may be the halo-extended array), under
    "rows" the device's query-row band, under "linear" the replicated
    global array.  All host tables a driver must feed through shard_map
    come from :meth:`shard_table_host`, :meth:`lut_sharded_host` and
    ``halo.send_recv_host()``.
    """

    def __init__(self, domain: BlockDomain, lowering: str = "closed_form",
                 batch_dims: Sequence[int] = (), storage: str = "embedded",
                 coarsen: int = 1, backend=None, *, mesh: Mesh, axis: str,
                 partition: Optional[str] = None, halo: bool = False):
        super().__init__(domain, lowering, batch_dims, storage, coarsen,
                         backend)
        self.mesh, self.axis = mesh, axis
        self.num_shards = int(mesh.shape[axis])
        if partition is None:
            partition = "storage-rows" if self.storage == "compact" \
                else "linear"
        if partition not in PARTITIONS:
            raise ValueError(f"unknown partition {partition!r}; expected "
                             f"one of {PARTITIONS}")
        #: None, or "interior" / "boundary" on a :meth:`phase_view`
        self.phase = None
        if partition == "storage-rows" and self.storage != "compact":
            raise ValueError("storage-rows partition requires compact "
                             "storage")
        if partition != "storage-rows" and self.storage == "compact":
            raise ValueError("compact storage shards its packed rows; "
                             f"partition {partition!r} is embedded-only")
        self.partition = partition
        D = self.num_shards
        if partition == "storage-rows":
            self.ncols, self.nrows = self._storage_grid()
            self.rpd = _ceil_div(self.nrows, D)
            self.nrows_pad = self.rpd * D
            N = self.sched_domain.num_blocks
            lo = np.minimum(np.arange(D) * self.rpd * self.ncols, N)
            self._lo = lo.astype(np.int64)
            self._count = np.minimum(
                N - lo, self.rpd * self.ncols).clip(min=0)
            self.steps_per_shard = self.rpd * self.ncols
            self.halo = memo.cached(
                "halo-plan", domain,
                (self.storage, self.coarsen, D, bool(halo)),
                lambda: HaloPlan(self, with_halo=halo))
        elif partition == "rows":
            nbx, nby = self.sched_domain.bounding_box
            by = self.sched_domain.coords_host()[:, 1]
            if np.any(np.diff(by) < 0):
                raise ValueError(
                    f"'rows' partition needs a query-row-major "
                    f"enumeration; {self.sched_domain.name} is not")
            self.rbd = _ceil_div(nby, D)
            row_lo = np.minimum(np.arange(D + 1) * self.rbd, nby)
            lo = np.searchsorted(by, row_lo, side="left")
            self._row_lo = row_lo[:-1].astype(np.int64)
            self._lo = lo[:-1].astype(np.int64)
            self._count = np.diff(lo).astype(np.int64)
            self.steps_per_shard = int(self._count.max())
            self.halo = None
        elif partition == "zigzag":
            nbx, nby = self.sched_domain.bounding_box
            coords = self.sched_domain.coords_host()
            by = coords[:, 1]
            if np.any(np.diff(by) < 0):
                raise ValueError(
                    f"'zigzag' partition needs a query-row-major "
                    f"enumeration; {self.sched_domain.name} is not")
            if nby % (2 * D):
                raise ValueError(
                    f"'zigzag' partition needs the query-block row count "
                    f"({nby}) divisible by 2 * num_shards ({2 * D}) for "
                    f"an exactly balanced snake")
            r = by % (2 * D)
            dev = np.minimum(r, 2 * D - 1 - r)
            local = 2 * (by // (2 * D)) + (r >= D)
            key = local.astype(np.int64) * nbx + coords[:, 0]
            self.rbd = nby // D
            self._zz_idx = []
            for d in range(D):
                sel = np.nonzero(dev == d)[0]
                self._zz_idx.append(
                    sel[np.argsort(key[sel], kind="stable")].astype(
                        np.int64))
            self._count = np.asarray(
                [len(s) for s in self._zz_idx], np.int64)
            self._lo = np.zeros(D, np.int64)
            self.steps_per_shard = int(self._count.max())
            self.halo = None
        else:  # linear
            N = self.sched_domain.num_blocks
            per = _ceil_div(N, D)
            lo = np.minimum(np.arange(D) * per, N)
            self._lo = lo.astype(np.int64)
            self._count = np.minimum(N - lo, per).clip(min=0)
            self.steps_per_shard = per
            self.halo = None

    # -- storage geometry ----------------------------------------------------

    def _storage_grid(self) -> Tuple[int, int]:
        """(ncols, nrows) of the scheduled storage grid: supertiles
        under coarsening, packed slots otherwise."""
        if self._tiling is not None:
            scols, srows = self.layout.grid_shape
            bw, bh = self._tiling.sub_shape
            return scols // bw, srows // bh
        return self.layout.grid_shape

    @property
    def row_unit(self) -> int:
        """Cells per storage row of one fine block row -- set by the
        driver via :meth:`bind_block`."""
        return self._row_unit

    def bind_block(self, block: int) -> "ShardedPlan":
        """Record the fine block size (cells); needed to convert storage
        rows to array rows for padding / halo exchange."""
        th, _ = self.supertile_shape((block, block))
        self._row_unit = th if self.storage == "compact" else block
        self._block = block
        return self

    def local_storage_shape(self, block: int) -> Tuple[int, int]:
        """Cell shape of one device's storage-array shard."""
        if self.storage == "embedded":
            return self.layout.embedded_shape(block)
        self.bind_block(block)
        _, tw = self.supertile_shape((block, block))
        return (self.rpd * self.row_unit, self.ncols * tw)

    def global_padded_rows(self, block: int) -> int:
        self.bind_block(block)
        return self.nrows_pad * self.row_unit

    def pad_rows(self, arr: jnp.ndarray, block: int) -> jnp.ndarray:
        """Zero-pad a global packed array to D-divisible storage rows."""
        rows = self.global_padded_rows(block)
        pad = rows - arr.shape[0]
        if pad == 0:
            return arr
        return jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))

    def unpad_rows(self, arr: jnp.ndarray, block: int) -> jnp.ndarray:
        scols, srows = self.layout.grid_shape
        return arr[:srows * block]

    # -- per-device tables ---------------------------------------------------

    def shard_table_host(self) -> np.ndarray:
        """(D, L) i32: one shard-table row per device (see SHARD_*);
        memoized per (domain, plan axes, D, partition, halo)."""
        return memo.cached(
            "shard-table", self.domain,
            (self.storage, self.coarsen, self.num_shards, self.partition,
             self.halo.h_max if self.halo is not None else -1),
            self._shard_table_host)

    def _shard_table_host(self) -> np.ndarray:
        cols = [self._row_lo_col(), self._count]
        if self.partition == "rows":
            cols.append(self._row_lo)
        elif self.partition == "zigzag":
            cols.append(np.arange(self.num_shards))
        tbl = np.stack([np.asarray(c, np.int64) for c in cols], -1)
        if self.partition == "storage-rows":
            tbl = np.concatenate([tbl, self.halo.ghost_map], axis=1)
        tbl = tbl.astype(np.int32)
        tbl.setflags(write=False)
        return tbl

    def _row_lo_col(self):
        if self.partition == "storage-rows":
            return np.arange(self.num_shards) * self.rpd
        return self._lo

    def lut_sharded_host(self) -> Optional[np.ndarray]:
        """(D * steps_per_shard, C) i32 decode table under prefetch_lut:
        the parent LUT re-ordered into each device's enumeration order,
        chunked per device and padded (pad rows repeat the chunk head so
        every read stays in-range; validity comes from the shard table's
        count).  Memoized per (domain, plan axes, D, partition)."""
        if self.lowering != "prefetch_lut":
            return None
        return memo.cached(
            "shard-lut", self.domain,
            (self.storage, self.coarsen, self.num_shards, self.partition),
            self._lut_sharded_host)

    def _lut_sharded_host(self) -> np.ndarray:
        base = GridPlan.lut_host(self)
        if self.partition == "storage-rows":
            if self._tiling is not None:
                slots = self._tiling.tiles_host()
            else:
                slots = self.layout.slots_host()
            order = np.argsort(
                slots[:, 1].astype(np.int64) * self.ncols + slots[:, 0],
                kind="stable")
            base = base[order]
        per = self.steps_per_shard
        out = np.zeros((self.num_shards, per, base.shape[1]), base.dtype)
        for d in range(self.num_shards):
            if self.partition == "zigzag":
                idx = self._zz_idx[d]
                c = len(idx)
                out[d] = base[idx[0]] if c else base[0]
                out[d, :c] = base[idx]
            else:
                lo, c = int(self._lo[d]), int(self._count[d])
                out[d] = base[lo] if c else base[0]
                out[d, :c] = base[lo:lo + c]
        out = out.reshape(self.num_shards * per, base.shape[1])
        out.setflags(write=False)
        return out

    def mma_table_sharded(self) -> Optional[jnp.ndarray]:
        """(D * steps_per_shard, C) i32 decode table of the table-backed
        ``mma`` lowering: the device-computed canonical chain table
        (:meth:`GridPlan.mma_table`), permuted/chunked/padded into the
        per-device enumeration order by a host-built gather index that
        replicates :meth:`_lut_sharded_host` exactly -- so the chunks
        carry chain-derived entries in LUT layout.  ``None`` under the
        other lowerings."""
        tbl = self.mma_table_sharded_host()
        return None if tbl is None else jnp.asarray(tbl)

    def mma_table_sharded_host(self) -> Optional[np.ndarray]:
        """Host numpy copy of :meth:`mma_table_sharded` (the verifier
        runs inside kernel jit traces, where the device gather would be
        a tracer)."""
        if self.lowering != "mma":
            return None
        idx = memo.cached(
            "shard-mma-index", self.domain,
            (self.storage, self.coarsen, self.num_shards, self.partition),
            self._mma_shard_index)
        return GridPlan.mma_table_host(self)[idx]

    def _mma_shard_index(self) -> np.ndarray:
        n = self.sched_domain.num_blocks
        order = np.arange(n, dtype=np.int64)
        if self.partition == "storage-rows":
            if self._tiling is not None:
                slots = self._tiling.tiles_host()
            else:
                slots = self.layout.slots_host()
            order = np.argsort(
                slots[:, 1].astype(np.int64) * self.ncols + slots[:, 0],
                kind="stable")
        per = self.steps_per_shard
        out = np.zeros((self.num_shards, per), np.int64)
        for d in range(self.num_shards):
            if self.partition == "zigzag":
                idx = self._zz_idx[d]
                c = len(idx)
                out[d] = idx[0] if c else 0
                out[d, :c] = idx
            else:
                lo, c = int(self._lo[d]), int(self._count[d])
                out[d] = order[lo] if c else order[0]
                out[d, :c] = order[lo:lo + c]
        out = out.reshape(self.num_shards * per)
        out.setflags(write=False)
        return out

    # -- interior/boundary phase views ---------------------------------------

    def phase_widths(self) -> Tuple[int, int]:
        """(max interior, max boundary) step counts over the devices --
        the static grid sizes of the two phase launches."""
        h = self.halo
        if h is None or h.int_steps is None:
            return 0, 0
        return (max((len(s) for s in h.int_steps), default=0),
                max((len(s) for s in h.bnd_steps), default=0))

    def phase_tables_host(self):
        """(interior, boundary) ``(D, 1 + max)`` i32 phase tables --
        ``[count, step ids...]`` per device, zero-padded (pad steps
        decode to step 0 and are masked by the count) -- or ``None``
        when either phase is empty everywhere, i.e. there is nothing
        to overlap."""
        mi, mb = self.phase_widths()
        if mi == 0 or mb == 0:
            return None

        def tbl(lists, m):
            out = np.zeros((self.num_shards, 1 + m), np.int32)
            for d, s in enumerate(lists):
                out[d, 0] = len(s)
                out[d, 1:1 + len(s)] = s
            out.setflags(write=False)
            return out
        return (tbl(self.halo.int_steps, mi),
                tbl(self.halo.bnd_steps, mb))

    def phase_view(self, which: str) -> "ShardedPlan":
        """A view of this plan whose grid covers only the interior or
        boundary steps: grid steps are indirected through one extra
        scalar-prefetch operand (the device's phase-table row, passed
        last), so the boundary launch -- the only one that reads ghost
        rows -- can start after the halo exchange while interior steps
        ran concurrently with it.  Both launches visit each owned step
        exactly once between them with unchanged operands, so the pair
        is bit-identical to the single synchronous launch."""
        if which not in ("interior", "boundary"):
            raise ValueError(f"unknown phase {which!r}")
        if self.partition != "storage-rows" or self.halo is None \
                or self.halo.int_steps is None:
            raise ValueError("phase views need a storage-rows plan "
                             "built with halo=True")
        if self.lowering == "bounding":
            raise ValueError("phase views reorder the step grid; the "
                             "bounding lowering is not step-indexed")
        import copy
        pv = copy.copy(self)
        pv.phase = which
        mi, mb = self.phase_widths()
        pv.steps_per_shard = mi if which == "interior" else mb
        return pv

    def _phase_step(self, t, refs):
        """Raw grid step -> scheduled step id: the phase table (last
        scalar-prefetch ref) indirects it on a phase view; identity
        otherwise."""
        if self.phase is None:
            return t
        return refs[-1][1 + t]

    def _phase_count(self, sref, refs):
        if self.phase is None:
            return sref[SHARD_COUNT]
        return refs[-1][0]

    # -- GridPlan overrides --------------------------------------------------

    @property
    def num_scalar_prefetch(self) -> int:
        base = 2 if self._table_backed else 1
        return base + (1 if self.phase is not None else 0)

    def bound_prefetch(self):
        return None  # per-device tables: the driver passes them

    def _lut_row0(self):
        return None  # per-device LUT chunks arrive as shard_map operands

    @property
    def grid(self):
        if self.lowering == "bounding":
            nbx, nby = self.sched_domain.bounding_box
            if self.partition in ("rows", "zigzag"):
                return self.batch_dims + (self.rbd, nbx)
            return self.batch_dims + (nby, nbx)
        return self.batch_dims + (self.steps_per_shard,)

    def _storage_coords(self, col, row):
        """Storage grid position (col, row) -> scheduled embedded block
        coords, the sharded closed-form decode (lambda on the orthotope
        coordinate; linear-order block_coords for block-linear
        layouts)."""
        if self._tiling is not None:
            t = self._tiling
            wx, wy = (col, row) if t.j % 2 == 0 else (row, col)
            return t.spec.lambda_map(wx, wy, t.coarse.r_b)
        spec = self.layout._fractal_spec()
        if spec is not None:
            return spec.lambda_map(col, row, self.domain.r_b)
        i = jnp.clip(row * self.ncols + col, 0,
                     self.sched_domain.num_blocks - 1)
        return self.sched_domain.block_coords(i)

    def _storage_row(self, bx, by):
        """Scheduled block coords -> its global storage row (traceable)."""
        if self._tiling is not None:
            return self._tiling.tile_index(bx, by)[1]
        return self.layout.slot(bx, by)[1]

    def _decode(self, grid_ids, prefetch_refs=()):
        nb = len(self.batch_dims)
        batch = tuple(grid_ids[:nb])
        sref = prefetch_refs[0]
        if self.lowering == "bounding":
            by, bx = grid_ids[nb], grid_ids[nb + 1]
            if self.partition == "rows":
                by = by + sref[SHARD_ROWLO]
            elif self.partition == "zigzag":
                by = self._zz_global_row(by, sref[SHARD_DEV])
            return batch, bx, by
        t = self._phase_step(grid_ids[nb], prefetch_refs)
        if self._table_backed:  # prefetch_lut, mma
            lut_ref = prefetch_refs[1]
            return batch, lut_ref[t, 0], lut_ref[t, 1]
        if self.partition == "zigzag":
            raise ValueError(
                "the zigzag partition's owned rows are scattered; its "
                "linear enumeration decodes through tables "
                "(prefetch_lut / mma) or the bounding grid")
        if self.partition == "storage-rows":
            col = t % self.ncols
            row = jnp.minimum(sref[SHARD_LO] + t // self.ncols,
                              self.nrows - 1)
            bx, by = self._storage_coords(col, row)
            return batch, bx, by
        # linear / rows: the parent enumeration at the device offset,
        # clamped into the device's own range so padded steps decode to
        # an owned (and discarded) block
        i = jnp.clip(sref[SHARD_LO]
                     + jnp.minimum(t, sref[SHARD_COUNT] - 1),
                     0, self.sched_domain.num_blocks - 1)
        return batch, *self.sched_domain.block_coords(i)

    def _zz_global_row(self, local, dev):
        """Local band row -> global query-block row of the snake."""
        two_d = 2 * self.num_shards
        return (local // 2) * two_d + jnp.where(
            local % 2 == 0, dev, two_d - 1 - dev)

    def _place_coords(self, bx, by, prefetch_refs=()):
        if self.partition == "rows":
            return bx, by - prefetch_refs[0][SHARD_ROWLO]
        if self.partition == "zigzag":
            two_d = 2 * self.num_shards
            return bx, 2 * (by // two_d) + (by % two_d >= self.num_shards)
        return bx, by

    def _step_valid(self, grid_ids, bx, by, prefetch_refs=()):
        sref = prefetch_refs[0]
        nb = len(self.batch_dims)
        if self.lowering != "bounding":
            return grid_ids[nb] < self._phase_count(sref, prefetch_refs)
        member = super()._step_valid(grid_ids, bx, by, prefetch_refs)
        owned = self._owned(sref, bx, by)
        return owned if member is None else member & owned

    def _owned(self, sref, bx, by):
        """Does this device own scheduled block (bx, by)?  Traceable;
        garbage for non-member coords (mask with membership first)."""
        if self.partition == "storage-rows":
            row = self._storage_row(bx, by)
            return (row >= sref[SHARD_LO]) \
                & (row < sref[SHARD_LO] + self.rpd)
        if self.partition == "rows":
            nby = self.sched_domain.bounding_box[1]
            return (by >= sref[SHARD_ROWLO]) \
                & (by < sref[SHARD_ROWLO] + self.rbd) & (by < nby)
        if self.partition == "zigzag":
            two_d = 2 * self.num_shards
            r = by % two_d
            nby = self.sched_domain.bounding_box[1]
            return (jnp.minimum(r, two_d - 1 - r) == sref[SHARD_DEV]) \
                & (by < nby)
        li = self.sched_domain.linear_index(bx, by)
        return (li >= sref[SHARD_LO]) \
            & (li < sref[SHARD_LO] + sref[SHARD_COUNT])

    def check_output_writeback(self) -> None:
        """Refuse ``bounding`` over compact storage on every target: a
        device's skipped steps (non-members, and members of other
        devices' slabs) have no owned member next to them in grid
        order, so their write-back would land on an owned slot."""
        if self.lowering == "bounding" and self.storage == "compact":
            raise ValueError(
                "grid_mode='bounding' with storage='compact' cannot be "
                "sharded: skipped steps would write back over owned "
                "slots; use closed_form, prefetch_lut or mma with "
                "compact storage, or embedded storage")

    # -- storage-array tile indices (local slab addressing) ------------------

    def storage_index(self, grid_ids, refs=()):
        """Local-slab tile index of the state operand (shared by the
        BlockSpec index maps and the pipelined kernels' DMA addressing,
        as in :meth:`GridPlan.storage_index`)."""
        if self.storage == "embedded":
            return super().storage_index(grid_ids, refs)
        if self.lowering == "bounding":
            _, bx, by = self._decode(grid_ids, refs)
            row = jnp.clip(self._storage_row(bx, by), 0,
                           self.nrows_pad - 1)
            loc = jnp.clip(refs[0][SHARD_GMAP + row], 0, self.rpd - 1)
            return loc, self._storage_col(bx, by)
        # the sharded enumerations are slab-row-major: the step index
        # addresses the local slab directly
        t = self._phase_step(grid_ids[len(self.batch_dims)], refs)
        return t // self.ncols, t % self.ncols

    def _storage_col(self, bx, by):
        if self._tiling is not None:
            return self._tiling.tile_index(bx, by)[0]
        return self.layout.slot(bx, by)[0]

    def neighbor_index(self, j: int, grid_ids, refs=()):
        if self.storage == "embedded":
            return super().neighbor_index(j, grid_ids, refs)
        dx, dy = NEIGHBOR_OFFSETS8[j]
        sref = refs[0]
        if self._table_backed:
            t = self._phase_step(grid_ids[len(self.batch_dims)], refs)
            lut_ref = refs[1]
            nsx = lut_ref[t, _LUT_NBR + 3 * j]
            nsy = lut_ref[t, _LUT_NBR + 3 * j + 1]
        else:
            _, bx, by = self._decode(grid_ids, refs)
            if self._tiling is not None:
                nsx, nsy, _ok = self._tiling.neighbor_tile(bx, by, dx, dy)
            else:
                nsx, nsy, _ok = self.layout.neighbor_slot(bx, by, dx, dy)
        row = jnp.clip(nsy, 0, self.nrows_pad - 1)
        return sref[SHARD_GMAP + row], nsx

    # -- ownership masks for the embedded psum combine -----------------------

    def owned_cell_mask(self, tbl, n: int, block: int) -> jnp.ndarray:
        """(n, n) bool inside shard_map: cells of member fine blocks
        whose *scheduled* block this device owns.  Ownership is disjoint
        and complete over member blocks, so masked psum combines are
        exact."""
        iy = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        ix = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        fbx, fby = ix // block, iy // block
        member = self.domain.contains(fbx, fby)
        sbx, sby = fbx // self.coarsen, fby // self.coarsen
        return member & self._owned(tbl, sbx, sby)

    def member_cell_block_mask(self, n: int, block: int) -> jnp.ndarray:
        """(n, n) bool: cells belonging to member fine blocks."""
        iy = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        ix = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        return self.domain.contains(ix // block, iy // block)


def zigzag_row_order(nby: int, num_shards: int) -> np.ndarray:
    """(nby,) permutation: position ``d * (nby // D) + l`` holds the
    global query-block row that device ``d``'s band row ``l`` owns
    under the snake assignment.  shard_map splits an operand into
    contiguous chunks, so a driver gathers Q block rows by this
    permutation before the sharded launch and scatters O back through
    its inverse (``np.argsort``) after."""
    D = num_shards
    if nby % (2 * D):
        raise ValueError(f"zigzag needs nby ({nby}) divisible by 2*D "
                         f"({2 * D})")
    perm = np.empty(nby, np.int64)
    rbd = nby // D
    for d in range(D):
        l = np.arange(rbd)
        perm[d * rbd:(d + 1) * rbd] = \
            (l // 2) * (2 * D) + np.where(l % 2 == 0, d, 2 * D - 1 - d)
    return perm


def device_tables(plan: ShardedPlan):
    """(shard_table, lut_tuple) device arrays for a driver's shard_map:
    the (D, L) shard table plus, under the table-backed lowerings
    (prefetch_lut, mma), the per-device decode
    table -- both sharded ``P(axis, None)`` on their leading axis so
    each device receives its own row/chunk.  One builder shared by
    every sharded kernel driver so the prefetch-operand plumbing cannot
    drift between kernels."""
    tbl = jnp.asarray(plan.shard_table_host())
    lut = plan.lut_sharded_host()
    if lut is not None:
        return tbl, (jnp.asarray(lut),)
    mma_tbl = plan.mma_table_sharded()
    return tbl, ((mma_tbl,) if mma_tbl is not None else ())
