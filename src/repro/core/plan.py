"""GridPlan: the unified block-space execution engine.

A ``GridPlan`` binds a :class:`~repro.core.domain.BlockDomain` (the
paper's compact parallel space and its lambda map) to one of three
*lowering strategies* and emits everything a Pallas kernel needs to run
over that domain:

* ``grid``        -- the launch grid (optionally with leading batch dims),
* ``index_map``   -- per-operand ``BlockSpec`` index maps built from one
                     shared decode of the grid step -> (bx, by),
* ``kernel coords`` -- the in-kernel ``(bx, by, valid)`` accessor,
* ``pallas_call`` -- a ``pl.pallas_call`` wrapper that hides the
                     lowering-specific grid-spec plumbing.

Lowerings
---------

``closed_form``
    The paper's per-block map: the grid has ``domain.num_blocks`` steps
    and each ``index_map`` evaluates ``domain.block_coords(t)`` inline
    (straight-line scalar math, unrolled at trace time).  The decode is
    defined once on the plan and shared by every operand's index map, so
    XLA/Mosaic CSE sees one digit-unrolling chain, not one per operand.

``prefetch_lut``
    The lookup-table realization (Navarro et al., "Efficient GPU Thread
    Mapping on Embedded 2D Fractals"): the host ``coords_host()`` table
    makes each decode an O(1) table read instead of the O(r) digit
    unrolling / integer-sqrt chain.  How the table travels is the
    backend's business (:mod:`repro.core.backend`): scalar prefetch on
    TPU, a regular HBM operand read at ``pl.program_id`` on GPU.
    Bit-identical to ``closed_form`` by construction: the table *is*
    the closed form, evaluated on host.

``bounding``
    The paper's baseline: launch the full bounding-box grid and discard
    non-member blocks at run time via ``domain.contains``.

``"compact"`` is accepted as a backward-compatible alias of
``closed_form`` (the name the kernels used before this engine existed).

Kernels written against a plan receive a :class:`BlockCoords` as their
first argument and are lowering-agnostic; any registered domain works in
any kernel under any lowering.

Superblock coarsening (``coarsen=s``)
-------------------------------------

``GridPlan(domain, ..., coarsen=s)`` makes each grid step own an s x s
embedded tile of fine blocks (s a power of the fractal's subdivision
factor): the grid enumerates the *coarse* domain (the same fractal at
level ``r - log_m s``), so the lambda decode runs once per superblock
and is amortized over its ``k**j`` member blocks.  ``storage_spec`` /
``neighbor_spec`` then emit supertile-sized BlockSpecs: a contiguous
(s*block)^2 region under embedded storage, or the contiguous
``k**ceil(j/2) x k**floor(j/2)`` fine-slot sub-rectangle of the packed
orthotope under compact storage (see
:class:`~repro.core.compact.SuperTiling`).  ``tile_map()`` /
``cell_offset_grids()`` give kernels the static packed<->embedded
fine-block permutation of one supertile.  See README "Scheduling".
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import backend as backend_lib
from . import fractal as F
from . import memo
from .domain import (BandDomain, BlockDomain, BoundingBoxDomain,
                     GeneralizedFractalDomain, SierpinskiDomain,
                     TriangularDomain)

LOWERINGS = ("closed_form", "prefetch_lut", "bounding", "mma")
_ALIASES = {"compact": "closed_form"}

STORAGES = ("embedded", "compact")

#: LUT column layout under ``storage="compact"``: the embedded (coarse)
#: block coords, the block's own packed slot / supertile index, then per
#: N/S/W/E/NW/NE/SW/SE neighbour (NEIGHBOR_OFFSETS8 order) the
#: (sx, sy, valid) triple -- 2 + 2 + 8*3 = 28 i32 columns.
_LUT_BX, _LUT_BY, _LUT_SX, _LUT_SY, _LUT_NBR = 0, 1, 2, 3, 4
_LUT_COLS = 28


def normalize_lowering(name: str) -> str:
    """Map user-facing lowering names (incl. legacy aliases) to canonical."""
    name = _ALIASES.get(name, name)
    if name not in LOWERINGS:
        raise ValueError(
            f"unknown lowering {name!r}; expected one of {LOWERINGS} "
            f"or aliases {tuple(_ALIASES)}")
    return name


def normalize_storage(name: str) -> str:
    if name not in STORAGES:
        raise ValueError(
            f"unknown storage {name!r}; expected one of {STORAGES}")
    return name


def xla_schedule(lowering: str) -> str:
    """The XLA-level flash-attention schedule equivalent to a lowering.

    ``closed_form``/``prefetch_lut``/``mma`` only launch member blocks
    -- the XLA mirror is the ``triangular`` (compact) schedule;
    ``bounding`` mirrors the ``dense`` masked schedule."""
    return "dense" if normalize_lowering(lowering) == "bounding" else \
        "triangular"


class BlockCoords:
    """In-kernel view of the current block: embedded coords + validity.

    ``bx``/``by``   -- embedded block coordinates (traced i32 scalars).
    ``batch``       -- tuple of leading batch-grid indices.
    ``valid``       -- membership predicate, or ``None`` when the plan
                       only enumerates member blocks (compact lowerings)
                       so no run-time discard is needed.
    ``first_step``  -- predicate for "is this the first grid step",
                       usable for one-time init of revisited outputs.
    ``grid_ids``    -- the raw grid indices of this step.
    ``refs``        -- the plan's decode-table refs, in operand order
                       (scalar-prefetch refs on TPU, leading HBM
                       operand refs on GPU).  gpu-structured kernels
                       pass these back into ``plan.storage_index`` /
                       ``plan.neighbor_index`` to address state tiles
                       themselves.
    """

    __slots__ = ("batch", "bx", "by", "valid", "first_step", "grid_ids",
                 "refs")

    def __init__(self, batch, bx, by, valid, first_step, grid_ids=(),
                 refs=()):
        self.batch = tuple(batch)
        self.bx = bx
        self.by = by
        self.valid = valid
        self.first_step = first_step
        self.grid_ids = tuple(grid_ids)
        self.refs = tuple(refs)

    def when_valid(self, body: Callable[[], None],
                   otherwise: Optional[Callable[[], None]] = None) -> None:
        """Run ``body`` for member blocks only (no-op guard when the
        lowering already guarantees membership), and ``otherwise``, when
        given, for the rest (see :attr:`GridPlan.skip_fills_output`)."""
        if self.valid is None:
            body()
            return
        pl.when(self.valid)(body)
        if otherwise is not None:
            pl.when(jnp.logical_not(self.valid))(otherwise)


class GridPlan:
    """Execution plan for one kernel launch over a block domain.

    Parameters
    ----------
    domain:      the block domain to enumerate.
    lowering:    "closed_form" | "prefetch_lut" | "bounding" | "mma"
                 (or the legacy alias "compact").  "mma" computes the
                 lambda decode as mixed-precision ``dot_general``
                 digit-basis chains (see :mod:`repro.core.mma`): on
                 block-indexed targets the chain output is bound as the
                 scalar-prefetch table, on gpu structures the chains
                 run in-kernel per program.
    batch_dims:  leading grid dimensions iterated outside the domain
                 (e.g. ``(batch * heads,)`` for attention).
    storage:     "embedded" (state arrays are the dense bounding-box
                 layout) or "compact" (state arrays live in the packed
                 O(n^H) orthotope layout of
                 :class:`~repro.core.compact.CompactLayout`; the
                 storage-array index maps emitted by ``storage_spec`` /
                 ``neighbor_spec`` address packed slots instead of
                 embedded block coords).
    coarsen:     s >= 1 embedded fine blocks per superblock side; s > 1
                 requires a fractal domain with s a power of its
                 subdivision factor.  The grid then enumerates the
                 coarse domain and every storage/neighbour spec covers
                 an s x s tile of fine blocks (the decode amortization
                 of Quezada et al.'s coarsening, on the block level).
    backend:     a :class:`~repro.core.backend.BackendTarget` (or its
                 name, or None = platform default): the emission
                 structure every ``pallas_call`` of this plan uses --
                 "tpu" (Mosaic scalar-prefetch), "gpu" (Triton,
                 in-kernel HBM addressing), or either "-interpret"
                 variant.
    """

    def __init__(self, domain: BlockDomain, lowering: str = "closed_form",
                 batch_dims: Sequence[int] = (), storage: str = "embedded",
                 coarsen: int = 1, backend=None):
        self.domain = domain
        self.lowering = normalize_lowering(lowering)
        self.batch_dims = tuple(int(d) for d in batch_dims)
        self.storage = normalize_storage(storage)
        self.target = backend_lib.resolve(backend)
        self.coarsen = int(coarsen)
        if self.coarsen < 1:
            raise ValueError(f"coarsen must be >= 1, got {coarsen}")
        if self.coarsen == 1:
            self._tiling = None
            #: the domain the *grid* enumerates (coarse under coarsening)
            self.sched_domain: BlockDomain = domain
        else:
            from .compact import super_tiling
            self._tiling = super_tiling(domain, self.coarsen)
            self.sched_domain = self._tiling.coarse
        self._layout = None

    @property
    def layout(self):
        """The domain's :class:`CompactLayout` (memoized per domain;
        available under either storage so callers can pack/unpack)."""
        if self._layout is None:
            from .compact import compact_layout
            self._layout = compact_layout(self.domain)
        return self._layout

    # -- grid ---------------------------------------------------------------

    @property
    def domain_dims(self) -> int:
        """How many trailing grid dimensions the domain occupies."""
        return 2 if self.lowering == "bounding" else 1

    @property
    def grid(self) -> Tuple[int, ...]:
        if self.lowering == "bounding":
            nbx, nby = self.sched_domain.bounding_box
            return self.batch_dims + (nby, nbx)
        return self.batch_dims + (self.sched_domain.num_blocks,)

    @property
    def num_steps(self) -> int:
        return int(np.prod(self.grid))

    # -- scalar-prefetch table ---------------------------------------------

    @property
    def _table_backed(self) -> bool:
        """Whether this plan's decode rides a bound table ref.

        ``prefetch_lut`` always does.  ``mma`` does only on
        block-indexed (TPU-structured) targets: Mosaic index maps
        cannot run ``dot_general``, so the chain output is bound as a
        scalar-prefetch table and read like a LUT; the gpu structure
        runs the chains in-kernel per program instead."""
        return self.lowering == "prefetch_lut" or (
            self.lowering == "mma" and self.target.block_indexed)

    @property
    def num_scalar_prefetch(self) -> int:
        return 1 if self._table_backed else 0

    def bound_prefetch(self):
        """The scalar-prefetch operands ``pallas_call`` binds itself, or
        ``None`` when the caller must supply them per call (the sharded
        planner: its tables are per-device shard_map operands, not trace
        constants)."""
        if not self.num_scalar_prefetch:
            return ()
        return (self.mma_table() if self.lowering == "mma"
                else self.lut(),)

    @staticmethod
    def _split_im_args(args, nsp: int):
        """Split an index_map's ``(*grid_ids, *prefetch_refs)`` arg list."""
        if nsp == 0:
            return tuple(args), ()
        return tuple(args[:-nsp]), tuple(args[-nsp:])

    def lut(self) -> jnp.ndarray:
        return jnp.asarray(self.lut_host())

    def lut_host(self) -> np.ndarray:
        """Host-built i32 decode table, one row per scheduled (member /
        coarse) block, memoized per (domain, storage, coarsen).

        embedded storage: (num_blocks, 2) of (bx, by).
        compact storage:  (num_blocks, 28): (bx, by, sx, sy) plus the
        eight (sx, sy, valid) neighbour-slot triples (NEIGHBOR_OFFSETS8
        order), so every compact address resolve -- including the 8-way
        CA halo gathers -- is an O(1) scalar-memory read.  Under
        ``coarsen`` the rows are coarse blocks and the slot columns are
        supertile indices (the rows widen per superblock, never per
        fine block: that is the amortization)."""
        return memo.cached("gridplan-lut", self.domain,
                           (self.storage, self.coarsen), self._lut_host)

    def _lut_host(self) -> np.ndarray:
        coords = self.sched_domain.coords_host()
        if self.storage == "embedded":
            return np.asarray(coords, np.int32)
        if self._tiling is not None:
            slots = self._tiling.tiles_host()
            nbrs = self._tiling.neighbor_tiles_host()
        else:
            slots = self.layout.slots_host()
            nbrs = self.layout.neighbor_slots_host()
        nbrs = nbrs.reshape(len(coords), 24)
        table = np.concatenate([coords, slots, nbrs],
                               axis=1).astype(np.int32)
        assert table.shape[1] == _LUT_COLS
        table.setflags(write=False)
        return table

    def mma_table(self) -> jnp.ndarray:
        """Decode table of the ``mma`` lowering -- the same row/column
        layout as :meth:`lut_host`, but every lambda / lambda^-1 entry
        is a :mod:`repro.core.mma` digit-basis ``dot_general`` chain
        instead of a host integer loop.  On block-indexed targets this
        is the bound scalar-prefetch operand (index maps read it like a
        LUT); the verifier re-derives it from ``linear_index`` ground
        truth, so a corrupted digit-basis matrix surfaces as table
        findings.  The memoized build runs the chains eagerly
        (``ensure_compile_time_eval``) so a first call inside a jit
        trace cannot cache tracers."""
        return jnp.asarray(self.mma_table_host())

    def mma_table_host(self) -> np.ndarray:
        """Host numpy copy of :meth:`mma_table` -- what the verifier
        re-derives against (it runs inside kernel jit traces, where the
        device array would be a tracer)."""
        return memo.cached("gridplan-mma-table", self.domain,
                           (self.storage, self.coarsen), self._mma_table)

    def _mma_table(self) -> np.ndarray:
        import jax

        with jax.ensure_compile_time_eval():
            table = np.asarray(self._mma_table_chains())
        table.setflags(write=False)
        return table

    def _mma_table_chains(self) -> jnp.ndarray:
        from . import mma
        from .compact import NEIGHBOR_OFFSETS8
        dom = self.sched_domain
        t = jnp.arange(dom.num_blocks, dtype=jnp.int32)
        frac = mma.fractal_of(dom)
        if frac is not None:
            spec, r = frac
            bx, by = mma.decode_linear(spec, r, t)
        else:
            bx, by = mma.decode_rows(dom, t)
        if self.storage == "embedded":
            return jnp.stack([bx, by], axis=-1).astype(jnp.int32)
        swap = self._tiling is not None and self._tiling.j % 2 == 1
        if frac is not None:
            sx, sy = mma.slots_of_linear(spec, r, t, swap=swap)
        else:
            # generic near-square layouts have no lambda to accelerate:
            # slots stay the integer row-major reshape of t.
            sx, sy = self.layout.slot(bx, by)
        cols = [bx, by, sx, sy]
        for dx, dy in NEIGHBOR_OFFSETS8:
            if frac is not None:
                nsx, nsy, ok = mma.neighbor_slots(
                    spec, r, dom, bx, by, dx, dy, swap=swap)
            else:
                nsx, nsy, ok = self.layout.neighbor_slot(bx, by, dx, dy)
            cols += [nsx, nsy, ok.astype(jnp.int32)]
        table = jnp.stack(cols, axis=-1).astype(jnp.int32)
        assert table.shape[1] == _LUT_COLS
        return table

    # -- the one shared decode ---------------------------------------------

    def _lut_row0(self) -> Optional[np.ndarray]:
        """Host copy of LUT row 0, when it is a trace constant (it is
        not for sharded plans: each device's table chunk starts at a
        different row, and the chunks are shard_map operands)."""
        return self.lut_host()[0]

    def _lut_read(self, lut_ref, t, col: int):
        """One LUT element read.  When ``t`` is a *static* step id --
        the DMA-pipeline prologues, which address steps 0..stages-2
        before the grid runs -- row 0 is host-known and folds to an
        immediate, so the first copies issue without waiting on the
        table load (the first-iteration LUT stall).  Traced steps read
        the table directly: a select would compute the same value but
        perturb XLA fusion, and the lowerings are contractually
        bit-identical."""
        if isinstance(t, (int, np.integer)) and int(t) == 0:
            row0 = self._lut_row0()
            if row0 is not None:
                return np.int32(row0[col])
        return lut_ref[t, col]

    def _decode(self, grid_ids, prefetch_refs=()):
        """grid step -> (batch_ids, bx, by) in the *scheduled* (coarse)
        block space.  Shared by every operand's index map and by the
        kernel prologue.  ``prefetch_refs`` holds the scalar-prefetch
        refs in operand order (the LUT is the last one here; the sharded
        planner prepends its per-device shard table)."""
        nb = len(self.batch_dims)
        batch = tuple(grid_ids[:nb])
        if self.lowering == "bounding":
            by, bx = grid_ids[nb], grid_ids[nb + 1]
        elif self._table_backed:  # prefetch_lut, or mma on TPU structures
            t = grid_ids[nb]
            lut_ref = prefetch_refs[-1]
            bx = self._lut_read(lut_ref, t, _LUT_BX)
            by = self._lut_read(lut_ref, t, _LUT_BY)
        elif self.lowering == "mma":  # gpu structure: chains in-kernel
            bx, by = self._mma_decode(grid_ids[nb])
        else:  # closed_form
            bx, by = self.sched_domain.block_coords(grid_ids[nb])
        return batch, bx, by

    def _mma_decode(self, t):
        """Linear step -> scheduled (bx, by) via the digit-basis matmul
        chains (fractal domains) or the row-comparison chain (generic
        row-major domains)."""
        from . import mma
        frac = mma.fractal_of(self.sched_domain)
        if frac is not None:
            return mma.decode_linear(frac[0], frac[1], t)
        return mma.decode_rows(self.sched_domain, t)

    def _place_coords(self, bx, by, prefetch_refs=()):
        """The (bx, by) an operand's ``place`` callback receives; the
        sharded planner localizes the row coordinate here."""
        return bx, by

    # -- per-operand index maps --------------------------------------------

    def index_map(self, place: Callable) -> Callable:
        """Build one operand's ``BlockSpec`` index map.

        ``place(bx, by, *batch_ids)`` returns the operand's block index
        tuple; the plan supplies the decoded coordinates with the arity
        and extra scalar-ref arguments each lowering requires."""
        nsp = self.num_scalar_prefetch

        def im(*args):
            grid_ids, refs = self._split_im_args(args, nsp)
            batch, bx, by = self._decode(grid_ids, refs)
            bx, by = self._place_coords(bx, by, refs)
            return place(bx, by, *batch)
        return im

    def block_spec(self, block_shape, place: Callable) -> pl.BlockSpec:
        return pl.BlockSpec(block_shape, self.index_map(place))

    # -- storage-array specs (embedded vs compact addressing) ---------------

    def supertile_shape(self, block_shape) -> Tuple[int, int]:
        """Cell shape of one storage supertile for fine ``block_shape``
        tiles: (s*b0, s*b1) embedded, (bh*b0, bw*b1) packed."""
        b0, b1 = block_shape
        if self.storage == "embedded" or self._tiling is None:
            return (self.coarsen * b0, self.coarsen * b1)
        bw, bh = self._tiling.sub_shape
        return (bh * b0, bw * b1)

    def tile_map(self):
        """Static packed->embedded fine-block permutation of one storage
        supertile as ``((oy, ox), (ey, ex))`` pairs, or ``None`` when
        the supertile is already embedded-arranged (embedded storage, or
        coarsen=1 where the tile is a single block)."""
        if self.storage == "embedded" or self._tiling is None:
            return None
        return self._tiling.tile_map()

    def cell_offset_grids(self, block: int):
        """(OY, OX) host i32 arrays shaped like the storage supertile:
        the embedded cell offset of every supertile cell relative to the
        superblock's embedded origin ``(by*s*block, bx*s*block)``.  For
        the trivial layouts this is a plain meshgrid; under compact
        coarsening it bakes the fine-block permutation in, so kernels
        evaluate membership masks directly on the packed arrangement."""
        tm = self.tile_map()
        if tm is None:
            h, w = self.supertile_shape((block, block))
            oy, ox = np.mgrid[0:h, 0:w]
            return oy.astype(np.int32), ox.astype(np.int32)
        h, w = self.supertile_shape((block, block))
        oy = np.zeros((h, w), np.int32)
        ox = np.zeros((h, w), np.int32)
        cy, cx = np.mgrid[0:block, 0:block]
        for (py, px), (ey, ex) in tm:
            oy[py * block:(py + 1) * block,
               px * block:(px + 1) * block] = ey * block + cy
            ox[py * block:(py + 1) * block,
               px * block:(px + 1) * block] = ex * block + cx
        return oy, ox

    def storage_index(self, grid_ids, refs=()):
        """(row, col) tile index of the state-array operand for one
        grid step: embedded -> the (super)block's (by, bx) in the
        bounding-box array; compact -> the packed slot (sy, sx) of the
        layout (the supertile sub-rectangle index under coarsening).
        Under ``prefetch_lut`` the slot is read from the extended LUT;
        the other lowerings evaluate ``layout.slot`` (lambda^-1)
        inline.  Shared by the ``BlockSpec`` index maps (TPU, where
        ``refs`` are scalar-prefetch refs) and the gpu-structured
        kernel bodies (where ``refs`` are the leading HBM operand refs
        and the returned index drives ``backend.load``/``backend.store``)."""
        if self.storage == "embedded":
            _, bx, by = self._decode(grid_ids, refs)
            bx, by = self._place_coords(bx, by, refs)
            return by, bx
        if self._table_backed:
            t = grid_ids[len(self.batch_dims)]
            lut_ref = refs[-1]
            return (self._lut_read(lut_ref, t, _LUT_SY),
                    self._lut_read(lut_ref, t, _LUT_SX))
        if self.lowering == "mma":
            from . import mma
            frac = mma.fractal_of(self.sched_domain)
            if frac is not None:
                # the compact enumeration is lambda-linear: the own slot
                # comes straight from the step id, one digit contraction
                swap = self._tiling is not None and self._tiling.j % 2
                sx, sy = mma.slots_of_linear(
                    frac[0], frac[1], grid_ids[len(self.batch_dims)],
                    swap=bool(swap))
                return sy, sx
        _, bx, by = self._decode(grid_ids, refs)
        if self.lowering == "bounding":
            # a skipped step revisits the block of the member step next
            # to it in grid order (see check_output_writeback)
            bx, by = self.sched_domain.nearest_row_member(bx, by)
        if self._tiling is not None:
            tx, ty = self._tiling.tile_index(bx, by)
            return ty, tx
        sx, sy = self.layout.slot(bx, by)
        return sy, sx

    def neighbor_index(self, j: int, grid_ids, refs=()):
        """(row, col) tile index of the j-th halo operand
        (``compact.NEIGHBOR_OFFSETS8`` order, j in [0, 8): N/S/W/E then
        the corners): the embedded neighbour (super)block clamped into
        range, or -- under compact storage -- its lambda^-1-resolved
        packed slot (slot (0, 0) for out-of-range / non-member
        neighbours; the kernel masks those contributions)."""
        from .compact import NEIGHBOR_OFFSETS8
        dx, dy = NEIGHBOR_OFFSETS8[j]
        if self.storage == "embedded":
            nbx, nby = self.sched_domain.bounding_box
            _, bx, by = self._decode(grid_ids, refs)
            bx, by = self._place_coords(bx, by, refs)
            return (jnp.clip(by + dy, 0, nby - 1),
                    jnp.clip(bx + dx, 0, nbx - 1))
        if self._table_backed:
            t = grid_ids[len(self.batch_dims)]
            lut_ref = refs[-1]
            return (self._lut_read(lut_ref, t, _LUT_NBR + 3 * j + 1),
                    self._lut_read(lut_ref, t, _LUT_NBR + 3 * j))
        _, bx, by = self._decode(grid_ids, refs)
        if self.lowering == "mma":
            from . import mma
            frac = mma.fractal_of(self.sched_domain)
            if frac is not None:
                swap = self._tiling is not None and self._tiling.j % 2
                sx, sy, _ok = mma.neighbor_slots(
                    frac[0], frac[1], self.sched_domain, bx, by, dx, dy,
                    swap=bool(swap))
                return sy, sx
        if self._tiling is not None:
            tx, ty, _ok = self._tiling.neighbor_tile(bx, by, dx, dy)
            return ty, tx
        sx, sy, _ok = self.layout.neighbor_slot(bx, by, dx, dy)
        return sy, sx

    def _index_spec(self, tile, index_fn) -> pl.BlockSpec:
        """Wrap an ``(grid_ids, refs) -> block index`` function as a
        BlockSpec with this plan's index-map arity."""
        nsp = self.num_scalar_prefetch

        def im(*args):
            grid_ids, refs = self._split_im_args(args, nsp)
            return index_fn(grid_ids, refs)
        return pl.BlockSpec(tile, im)

    def storage_spec(self, block_shape) -> pl.BlockSpec:
        """BlockSpec for a 2-D state-array operand under this plan's
        storage (see :meth:`storage_index`).  ``block_shape`` is the
        *fine* block shape; the emitted spec's block is the
        supertile."""
        return self._index_spec(self.supertile_shape(block_shape),
                                self.storage_index)

    def neighbor_spec(self, block_shape, j: int) -> pl.BlockSpec:
        """BlockSpec for the j-th halo operand (see
        :meth:`neighbor_index`)."""
        return self._index_spec(
            self.supertile_shape(block_shape),
            lambda grid_ids, refs: self.neighbor_index(j, grid_ids, refs))

    # -- output write-back on the chip ---------------------------------------

    @property
    def skip_fills_output(self) -> bool:
        """Whether a kernel must fill the output block of a skipped step
        (``coords.valid`` false).  The chip writes every visited output
        block back without reading it first.  Under ``bounding`` over
        embedded storage each skipped step owns its block, so the
        kernel passes it through.  The other skipped steps revisit the
        block of a member step next to them in grid order (compact
        storage under ``bounding``, the padding of sharded
        enumerations), which must be left as that step writes it."""
        return self.lowering == "bounding" and self.storage == "embedded"

    def check_output_writeback(self) -> None:
        """Refuse, on every target, a plan whose skipped steps would
        write back over blocks that member steps own.  ``bounding`` over
        compact storage sends each non-member step to the slot of the
        member step next to it in grid order
        (:meth:`~repro.core.domain.BlockDomain.nearest_row_member`), so
        its domain must be able to name that member."""
        if self.lowering == "bounding" and self.storage == "compact":
            try:
                self.sched_domain.nearest_row_member(0, 0)
            except NotImplementedError as e:
                raise ValueError(
                    f"grid_mode='bounding' with storage='compact' needs "
                    f"a domain that names its row members ({e}); use "
                    f"closed_form, prefetch_lut or mma") from None

    # -- in-kernel accessor -------------------------------------------------

    def kernel_coords(self, *prefetch_refs) -> BlockCoords:
        grid_ids = tuple(pl.program_id(i) for i in range(len(self.grid)))
        batch, bx, by = self._decode(grid_ids, prefetch_refs)
        valid = self._step_valid(grid_ids, bx, by, prefetch_refs)
        first = grid_ids[0] == 0
        for g in grid_ids[1:]:
            first = first & (g == 0)
        return BlockCoords(batch, bx, by, valid, first, grid_ids,
                           prefetch_refs)

    def _step_valid(self, grid_ids, bx, by, prefetch_refs=()):
        """The membership/ownership predicate of one grid step (``None``
        when every step is live)."""
        if self.lowering == "bounding" and not getattr(
                self.sched_domain, "always_member", False):
            return self.sched_domain.contains(bx, by)
        return None

    # -- pallas_call wrapper ------------------------------------------------

    def pallas_call(self, kernel: Callable, *, in_specs, out_specs,
                    out_shape, scratch_shapes=(),
                    input_output_aliases: Optional[dict] = None,
                    interpret: Optional[bool] = None,
                    **kwargs) -> Callable:
        """Emit the ``pl.pallas_call`` for this plan on its
        :class:`~repro.core.backend.BackendTarget` (see
        :func:`repro.core.backend.emit`, which owns all grid-spec
        construction).  ``kernel(coords, *refs)`` is lowering- and
        backend-agnostic at the signature level; gpu-structured kernels
        additionally address state through ``coords.grid_ids`` /
        ``coords.refs`` and :meth:`storage_index` /
        :meth:`neighbor_index`.  ``interpret=None`` defers to the
        target's interpret flag (an explicit bool overrides, for
        tests)."""
        return backend_lib.emit(
            self, kernel, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            input_output_aliases=input_output_aliases,
            interpret=interpret, **kwargs)

    # -- grid-step helpers for gpu-structured kernels ------------------------

    @property
    def steps_per_launch(self) -> int:
        """Grid steps per batch element (the domain grid volume): the
        partial-result axis gpu-structured reductions emit, one slot
        per step, before the deterministic host-side combine."""
        nb = len(self.batch_dims)
        out = 1
        for d in self.grid[nb:]:
            out *= int(d)
        return out

    def linear_step(self, grid_ids):
        """Flatten the (possibly 2-D, under ``bounding``) domain grid
        indices of one step to a linear step id in
        [0, steps_per_launch)."""
        nb = len(self.batch_dims)
        if self.lowering == "bounding":
            nbx = int(self.grid[nb + 1])
            return grid_ids[nb] * nbx + grid_ids[nb + 1]
        return grid_ids[nb]

    def grid_ids_at(self, lin, batch=()):
        """Inverse of :meth:`linear_step`: the full grid-index tuple of
        linear domain step ``lin`` under the given batch ids.  ``lin``
        may be traced (pipelined kernels addressing step t+s ahead of
        the grid) or a Python int (launch prologues, where static step
        ids let the decode constant-fold)."""
        batch = tuple(batch)
        if len(batch) != len(self.batch_dims):
            raise ValueError(
                f"expected {len(self.batch_dims)} batch ids, "
                f"got {len(batch)}")
        if self.lowering == "bounding":
            nbx = int(self.grid[len(batch) + 1])
            return batch + (lin // nbx, lin % nbx)
        return batch + (lin,)

    # -- host-side geometry helpers ----------------------------------------

    def row_extents(self) -> np.ndarray:
        """(nby, 2) i32 host array of [min_bx, max_bx] per block row.

        Rows with no member blocks get [0, -1].  This is the per-row
        k-extent the XLA-level flash schedules consume (the block-space
        work-saving of Theorem 2 applied row-wise).  One vectorized
        pass over the table, O(num_blocks)."""
        nbx, nby = self.domain.bounding_box
        lo = np.full((nby,), nbx, np.int64)
        hi = np.full((nby,), -1, np.int64)
        coords = self.domain.coords_host()
        np.minimum.at(lo, coords[:, 1], coords[:, 0])
        np.maximum.at(hi, coords[:, 1], coords[:, 0])
        lo[hi < 0] = 0
        return np.stack([lo, hi], -1).astype(np.int32)


# ---------------------------------------------------------------------------
# Domain registry: every compact domain the engine knows how to lower.
# Used by the equivalence tests and the lowering A/B benchmarks.
# ---------------------------------------------------------------------------

def registered_domains(size: str = "small") -> dict:
    """Representative instances of every registered domain family.

    size: "small" (fast interpret-mode tests) or "medium"."""
    if size == "small":
        return {
            "sierpinski": SierpinskiDomain(8),
            "carpet": GeneralizedFractalDomain(F.CARPET, 9),
            "vicsek": GeneralizedFractalDomain(F.VICSEK, 9),
            "triangular": TriangularDomain(6),
            "band": BandDomain(8, 3),
            "bounding-box": BoundingBoxDomain(4, 3),
        }
    return {
        "sierpinski": SierpinskiDomain(32),
        "carpet": GeneralizedFractalDomain(F.CARPET, 27),
        "vicsek": GeneralizedFractalDomain(F.VICSEK, 27),
        "triangular": TriangularDomain(17),
        "band": BandDomain(24, 5),
        "bounding-box": BoundingBoxDomain(7, 5),
    }
