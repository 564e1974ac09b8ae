"""Backend-neutral kernel emission: the ``BackendTarget`` capability
descriptor plus the one place in the repo that constructs Pallas grid
specs.

The paper reports its lambda(omega) speedups on GPUs, but the execution
engine grew up against TPU Pallas: scalar-prefetch decode tables
(``pltpu.PrefetchScalarGridSpec``), SMEM scalar operands, and the
sequential-grid revisiting idiom are Mosaic-specific, and everywhere
else the kernels silently fell back to interpret mode.  This module
gives the engine a real backend axis:

``tpu`` (Mosaic)
    The existing path, unchanged semantics: operand placement happens in
    ``BlockSpec`` index maps, which may read host-built decode tables
    through scalar prefetch; run-time scalars ride SMEM refs; the grid
    is sequential, so revisited output blocks accumulate across steps
    and online-softmax state lives in VMEM scratch.

``gpu`` (Triton / ``pallas.gpu``)
    No scalar prefetch and no sequential-grid guarantee, so the same
    plans lower the way the paper's CUDA kernels (and the follow-up GPU
    thread-mapping work, arXiv:2004.13475) do: the per-block
    lambda / slot / neighbour LUT travels as a **regular HBM operand**
    read in-kernel at ``pl.program_id``; state arrays arrive whole and
    kernels address tiles with computed offsets (:func:`load` /
    :func:`store`); run-time step counts are ordinary scalar operands;
    reduction state lives in loop carries, not scratch.  On a CUDA
    device the call lowers through Triton with ``num_warps`` /
    ``num_stages`` from the autotuner.

``tpu-interpret`` / ``gpu-interpret``
    Either structure executed by the Pallas interpreter -- selectable
    in CI so both lowerings are exercised (and cross-checked
    bit-for-bit) without the hardware.

Selection order for the default target: an explicit ``backend=``
argument > :func:`set_default` > the ``REPRO_BACKEND`` environment
variable > the jax platform (tpu -> ``tpu``, gpu -> ``gpu``, anything
else -> ``tpu-interpret``, preserving the historical CPU behaviour).
A native target named explicitly stays native on every platform: off
its chip it fails to lower instead of being emulated unseen.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: environment override consulted by :func:`resolve` (CI's gpu-backend
#: job sets ``REPRO_BACKEND=gpu-interpret``).
BACKEND_ENV = "REPRO_BACKEND"

_OVERRIDE: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class BackendTarget:
    """Capability descriptor for one kernel-emission target.

    Fields are the capabilities the kernels and plans actually branch
    on -- nothing here is advisory:

    kind:                "tpu" (Mosaic) or "gpu" (Triton) emission
                         structure.
    interpret:           run the structure under the Pallas interpreter.
    has_scalar_prefetch: BlockSpec index maps may read host decode
                         tables (``PrefetchScalarGridSpec``).  Without
                         it, tables become leading HBM operands read
                         in-kernel.
    smem_scalar_params:  run-time scalars (fused step counts, decode
                         positions) ride SMEM refs; otherwise they are
                         regular (1,) i32 operands.
    block_indexed:       operand tiles are placed by BlockSpec index
                         maps (the grid-sequenced Mosaic pipeline);
                         otherwise state arrays arrive whole and the
                         kernel computes tile offsets itself.
    sequential_grid:     grid steps execute in order, so revisited
                         output blocks may accumulate across steps and
                         per-row state may live in scratch.  GPU grids
                         are parallel: reductions must use loop carries
                         or per-step partials.
    supports_scratch:    ``scratch_shapes`` (VMEM accumulators) exist.
    memory_space:        where operand tiles land ("vmem" pipeline
                         copies vs "hbm" pointers) -- documentation of
                         the model each structure assumes.
    async_copy:          kernels may issue explicit in-kernel DMA
                         (``pltpu.make_async_copy`` + DMA semaphores,
                         operands parked in ``pl.ANY``) and overlap
                         the copy with compute.  Mosaic has DMA
                         engines; the interpreter emulates the copies
                         synchronously, preserving semantics.
    pipeline_stages:     maximum useful staged-copy depth for
                         software-pipelined streaming loops: the DMA
                         double buffers of the TPU structure (2) and
                         the FIFO/Triton stages of the GPU structure
                         (4, quad buffering).  1 means the target has
                         no software pipeline: ``resolve_stages``
                         clamps every request back to the synchronous
                         path.
    prefers_mma:         the target has matrix units (MXU / tensor
                         cores) that make the ``mma`` digit-basis
                         decode chains profitable; the autotuner ranks
                         ``mma`` candidates first on such targets.
                         Both structures carry the flag (TPUs have the
                         MXU, GPUs tensor cores); a scalar-only target
                         would clear it.
    smem_table_bytes:    SMEM the scalar-prefetch tables of one launch
                         may fill together, or None where nothing bounds
                         them (the interpreter, the gpu structure).
                         Tables over it are refused when the launch is
                         traced (:func:`check_smem_tables`).
    """

    name: str
    kind: str
    interpret: bool
    has_scalar_prefetch: bool
    smem_scalar_params: bool
    block_indexed: bool
    sequential_grid: bool
    supports_scratch: bool
    memory_space: str
    async_copy: bool
    pipeline_stages: int
    prefers_mma: bool
    smem_table_bytes: Optional[int]

    # -- variants -----------------------------------------------------------

    def emulated(self) -> "BackendTarget":
        """This structure under the interpreter (idempotent; returns
        the canonical singleton)."""
        if self.interpret:
            return self
        return TARGETS[self.name + "-interpret"]

    def native(self) -> "BackendTarget":
        if not self.interpret:
            return self
        return TARGETS[self.kind]

    # -- emission helpers ---------------------------------------------------

    def scalar_spec(self) -> pl.BlockSpec:
        """BlockSpec for a run-time scalar operand (shape (1,) i32):
        an SMEM ref on TPU, a regular operand elsewhere."""
        if self.smem_scalar_params:
            return pl.BlockSpec(memory_space=pltpu.SMEM)
        return full_spec((1,))

    def scratch(self, shape, dtype):
        """A VMEM scratch allocation, where the target has scratch."""
        if not self.supports_scratch:
            raise ValueError(
                f"target {self.name!r} has no scratch memory: keep "
                f"reduction state in loop carries")
        return pltpu.VMEM(shape, dtype)

    # -- software pipelining ------------------------------------------------

    def resolve_stages(self, num_stages: Optional[int]) -> int:
        """Clamp a requested pipeline depth to what this target can
        stage.  ``None`` / ``"auto"`` and anything <= 1 mean the
        synchronous path; depths beyond :attr:`pipeline_stages` clamp
        down rather than error so a tune-cache entry from a deeper
        target stays usable."""
        if num_stages is None or num_stages == "auto":
            return 1
        return max(1, min(int(num_stages), self.pipeline_stages))

    def any_spec(self) -> pl.BlockSpec:
        """BlockSpec parking an operand un-copied (``pl.ANY``) so
        the kernel streams tiles out of it with explicit DMA.  Only
        meaningful on :attr:`async_copy` targets."""
        if not self.async_copy:
            raise ValueError(
                f"target {self.name!r} has no async-copy support; "
                f"operands must arrive via BlockSpec pipeline copies")
        return pl.BlockSpec(memory_space=pl.ANY)

    def dma_sems(self, shape) -> object:
        """A scratch array of DMA-completion semaphores (one per
        in-flight copy slot)."""
        if not self.async_copy:
            raise ValueError(
                f"target {self.name!r} has no DMA semaphores")
        return pltpu.SemaphoreType.DMA(tuple(shape))

    @staticmethod
    def start_copy(src, dst, sem):
        """Begin ``src -> dst`` on a DMA engine; returns the copy
        descriptor (``.wait()`` blocks on ``sem``).  The interpreter
        performs the copy synchronously at ``start``/``wait``."""
        return pltpu.make_async_copy(src, dst, sem)

    def call_kwargs(self, num_warps: Optional[int] = None,
                    num_stages: Optional[int] = None) -> dict:
        """Extra ``pl.pallas_call`` kwargs for this target (the Triton
        compiler parameters, when actually compiling for a GPU)."""
        if self.kind == "gpu" and not self.interpret:
            from jax.experimental.pallas import triton as pltriton
            return {"compiler_params": pltriton.TritonCompilerParams(
                num_warps=int(num_warps or 4),
                num_stages=int(num_stages or 2))}
        return {}


#: SMEM a kernel's scalar-prefetch tables may fill together: the 1 MiB
#: of a TPU v5e core, less 16 KiB kept for the compiler's own scalars.
#: Each table is placed in whole 4 KiB pages.
SMEM_TABLE_BYTES = (1 << 20) - (16 << 10)
_SMEM_PAGE = 4096


def _mk(name, kind, interpret):
    tpu = kind == "tpu"
    return BackendTarget(
        name=name, kind=kind, interpret=interpret,
        has_scalar_prefetch=tpu, smem_scalar_params=tpu,
        block_indexed=tpu, sequential_grid=tpu, supports_scratch=tpu,
        memory_space="vmem" if tpu else "hbm",
        # capability flags are per *structure*, not per execution mode:
        # the -interpret variants keep them so the pipelined paths are
        # exercised (and parity-tested) without the hardware.
        async_copy=tpu, pipeline_stages=2 if tpu else 4,
        prefers_mma=True,
        # the chip's budget; the interpreter keeps tables in host memory
        smem_table_bytes=SMEM_TABLE_BYTES if tpu and not interpret
        else None)


TPU = _mk("tpu", "tpu", False)
GPU = _mk("gpu", "gpu", False)
TPU_INTERPRET = _mk("tpu-interpret", "tpu", True)
GPU_INTERPRET = _mk("gpu-interpret", "gpu", True)

TARGETS = {t.name: t for t in (TPU, GPU, TPU_INTERPRET, GPU_INTERPRET)}
_ALIASES = {"mosaic": "tpu", "triton": "gpu"}


def platform_default() -> BackendTarget:
    """The target the bare jax platform implies, ignoring
    :func:`set_default` and ``REPRO_BACKEND``.  This is the reference
    point for *persisted* qualification (tune-cache keys): a process
    whose default was steered away from the platform must stamp its
    entries, or another process with a different default would read
    them as its own."""
    plat = jax.default_backend()
    return TPU if plat == "tpu" else (
        GPU if plat == "gpu" else TPU_INTERPRET)


def set_default(name: Optional[str]) -> None:
    """Process-wide default target override (the ``--backend`` flag of
    serve/train); ``None`` restores platform/env selection."""
    global _OVERRIDE
    if name is not None:
        resolve(name)  # validate eagerly
    _OVERRIDE = name


def resolve(spec=None, interpret: Optional[bool] = None) -> BackendTarget:
    """Normalize a backend spec to a :class:`BackendTarget`.

    spec: a target (returned as given), a name ("tpu" | "gpu" |
    "*-interpret" | "interpret" = platform default emulated), or None
    (defaulting rules in the module docstring).  ``interpret=True``
    forces emulation; ``interpret=False`` pins the native structure.
    With ``interpret`` unset the named target is kept as it is: a
    native target is never emulated behind the caller's back, so a
    compile rehearsal off the chip reaches the real compiler.
    """
    if isinstance(spec, BackendTarget):
        target = spec
    else:
        if spec is None:
            spec = _OVERRIDE or os.environ.get(BACKEND_ENV) or None
        if spec is None:
            target = platform_default()
        else:
            name = _ALIASES.get(spec, spec)
            if name == "interpret":
                plat = jax.default_backend()
                target = (GPU if plat == "gpu" else TPU).emulated()
            elif name in TARGETS:
                target = TARGETS[name]
            else:
                raise ValueError(
                    f"unknown backend {spec!r}; expected one of "
                    f"{tuple(TARGETS)} or {tuple(_ALIASES)} or "
                    f"'interpret'")
    if interpret is True:
        return target.emulated()
    if interpret is False:
        return target.native()
    return target


def load(ref, idx):
    """Read the window ``idx`` (a tuple of ``pl.ds`` / ints) of ``ref``:
    the gpu structure's computed-offset tile read.  The access
    sanitizer replaces this function (and :func:`store`) to record
    every window a launch touches."""
    return ref[idx]


def store(ref, idx, val) -> None:
    """Write ``val`` into the window ``idx`` of ``ref`` (see
    :func:`load`)."""
    ref[idx] = val


def stream_tiles(src_ref, bufs_ref, sems, *, srcs_for, lin, total,
                 stages):
    """One sequential-grid step of software-pipelined tile streaming
    (the TPU structure's async-copy double/multi buffer).

    ``src_ref`` is the state parked whole in ``pl.ANY``;
    ``bufs_ref`` is VMEM scratch ``(stages, n_tiles, th, tw)`` and
    ``sems`` a matching ``(stages, n_tiles)`` DMA semaphore array.
    ``srcs_for(step)`` returns the (tile_row, tile_col) indices of the
    ``n_tiles`` tiles step ``step`` consumes (``step`` may be a traced
    scalar or a static int -- prologue decodes constant-fold).

    Grid step ``lin`` (of ``total``) waits on its own copies -- started
    ``stages - 1`` steps earlier, or in the step-0 prologue -- then
    starts the copies for step ``lin + stages - 1`` so they fly during
    this step's compute, and returns the current tiles.  Tile indices
    are clamped into the source's range, so prefetches past the grid
    (and fetches of masked-off neighbour slots) read in-bounds garbage
    that the caller's validity masking discards.  Consumption order is
    exactly the synchronous order: results are bit-identical."""
    n_tiles, th, tw = (int(bufs_ref.shape[1]), int(bufs_ref.shape[2]),
                       int(bufs_ref.shape[3]))
    nr = int(src_ref.shape[0]) // th
    nc = int(src_ref.shape[1]) // tw

    def copy(slot, j, ty, tx):
        ty = jnp.clip(ty, 0, nr - 1)
        tx = jnp.clip(tx, 0, nc - 1)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(ty * th, th), pl.ds(tx * tw, tw)],
            bufs_ref.at[slot, j], sems.at[slot, j])

    def start_all(step, slot):
        for j, (ty, tx) in enumerate(srcs_for(step)):
            copy(slot, j, ty, tx).start()

    @pl.when(lin == 0)
    def _():
        # prologue: fill the first stages-1 buffer slots (static step
        # ids, so the step-0 decode folds to constants)
        for i in range(min(stages - 1, total)):
            start_all(i, i)

    nxt = lin + (stages - 1)

    @pl.when(nxt < total)
    def _():
        start_all(jnp.minimum(nxt, total - 1), jax.lax.rem(nxt, stages))

    slot = jax.lax.rem(lin, stages)
    tiles = []
    for j, (ty, tx) in enumerate(srcs_for(lin)):
        copy(slot, j, ty, tx).wait()
        tiles.append(bufs_ref[slot, j])
    return tiles


def full_spec(shape) -> pl.BlockSpec:
    """BlockSpec handing the kernel the whole operand (the GPU targets'
    HBM-resident view: one block covering the array, pinned at the
    origin for every grid step)."""
    nd = len(shape)
    return pl.BlockSpec(tuple(shape), lambda *_: (0,) * nd)


# ---------------------------------------------------------------------------
# scalar-prefetch tables in SMEM
# ---------------------------------------------------------------------------



class RowMajor:
    """2-D read view of a flattened scalar-prefetch table ref.

    Mosaic pads every row of a 2-D SMEM array to 128 words, so a
    (rows, 2) decode table would take 64x its size; tables therefore
    ride flat and ``view[i, j]`` reads ``ref[i * cols + j]``."""

    __slots__ = ("ref", "shape")

    def __init__(self, ref, shape):
        self.ref = ref
        self.shape = tuple(shape)

    def __getitem__(self, idx):
        i, j = idx
        return self.ref[i * self.shape[1] + j]


def check_smem_tables(shapes, plan=None,
                      limit: int = SMEM_TABLE_BYTES) -> None:
    """Refuse scalar-prefetch tables that would not fit ``limit`` bytes
    of SMEM, naming the limit, before anything is lowered.  ``shapes``
    are the i32 table shapes of one launch."""
    need = sum(-(-4 * int(np.prod(s)) // _SMEM_PAGE) * _SMEM_PAGE
               for s in shapes)
    if need > limit:
        what = (f"{plan.lowering} plan over {type(plan.domain).__name__}"
                f" ({plan.storage} storage)" if plan is not None
                else "launch")
        raise ValueError(
            f"scalar-prefetch tables {list(shapes)} of this {what} need "
            f"{need} B of SMEM; the limit is {limit} B.  Use a lowering "
            f"without a table (closed_form, bounding) or a coarser "
            f"block for this size.")


# ---------------------------------------------------------------------------
# emission observer (the access sanitizer's hook)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EmitRecord:
    """What one :func:`emit` call is about to lower -- handed to the
    installed emit hook so it can instrument the launch (the analysis
    sanitizer wraps index maps and the kernel body) and observe calls.
    ``aliases`` is the array-operand-keyed mapping, before the table
    shift."""

    plan: object
    in_specs: tuple
    out_specs: object
    out_shape: object
    aliases: dict
    nsp: int
    interpret: bool


_EMIT_HOOK = None


def set_emit_hook(hook):
    """Install an emission observer; returns the previous hook.  The
    hook sees every *interpreted* launch: ``instrument(record, kernel,
    in_specs, out_specs)`` may return replacements, and ``wrap_call``
    wraps the emitted callable.  ``None`` uninstalls."""
    global _EMIT_HOOK
    prev = _EMIT_HOOK
    _EMIT_HOOK = hook
    return prev


# ---------------------------------------------------------------------------
# the emitter: every plan-driven pallas_call in the repo goes through
# here, and this is the only module that constructs a grid spec.
# ---------------------------------------------------------------------------

def emit(plan, kernel: Callable, *, in_specs, out_specs, out_shape,
         scratch_shapes=(), input_output_aliases: Optional[dict] = None,
         interpret: Optional[bool] = None,
         num_warps: Optional[int] = None,
         num_stages: Optional[int] = None, **kwargs) -> Callable:
    """Build the ``pl.pallas_call`` for ``plan`` on its target.

    ``kernel(coords, *refs)`` is lowering- and target-agnostic at the
    signature level; the wrapper injects the decoded
    :class:`~repro.core.plan.BlockCoords` and routes the plan's decode
    tables (``plan.num_scalar_prefetch`` of them) the way the target
    supports:

    * scalar prefetch (TPU): ``PrefetchScalarGridSpec``, tables
      readable from index maps and the kernel prologue;
    * regular operands (GPU): tables become leading full-array HBM
      operands -- index maps cannot see them, so gpu-structured kernels
      do their own tile addressing via ``plan.storage_index`` /
      ``plan.neighbor_index`` with ``coords.grid_ids`` /
      ``coords.refs``.

    ``input_output_aliases`` is keyed on the *array* operands (tables
    excluded); the emitter shifts it.  When :meth:`plan.bound_prefetch`
    returns tables the returned callable takes just the array operands;
    when it returns ``None`` the caller passes the tables first
    (sharded plans, whose tables are per-device ``shard_map``
    operands).
    """
    target = plan.target
    interp = target.interpret if interpret is None else interpret
    if scratch_shapes and not target.supports_scratch:
        raise ValueError(
            f"target {target.name!r} has no scratch memory; "
            f"gpu-structured kernels keep state in loop carries")
    aliases = {int(i): int(o)
               for i, o in (input_output_aliases or {}).items()}
    nsp = plan.num_scalar_prefetch
    extra = dict(kwargs)
    extra.update(target.call_kwargs(num_warps, num_stages))

    record = None
    if _EMIT_HOOK is not None and interp:
        record = EmitRecord(plan=plan, in_specs=tuple(in_specs),
                            out_specs=out_specs, out_shape=out_shape,
                            aliases=dict(aliases), nsp=nsp,
                            interpret=interp)
        kernel, in_specs, out_specs = _EMIT_HOOK.instrument(
            record, kernel, in_specs, out_specs)
        hook = _EMIT_HOOK

        def _wrap(fn):
            return hook.wrap_call(record, fn)
    else:
        def _wrap(fn):
            return fn

    if nsp == 0:
        def wrapped(*refs):
            kernel(plan.kernel_coords(), *refs)

        call = pl.pallas_call(
            wrapped, grid=plan.grid, in_specs=list(in_specs),
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=list(scratch_shapes),
            input_output_aliases=aliases, interpret=interp, **extra)
        return _wrap(lambda *operands: call(*operands))

    def wrapped(*args):
        kernel(plan.kernel_coords(*args[:nsp]), *args[nsp:])

    # operand indices count the tables as inputs 0..nsp either way
    aliases = {i + nsp: o for i, o in aliases.items()}

    if target.has_scalar_prefetch:
        def call(*args):
            # tables ride flat (see RowMajor); their shapes are known
            # only at call time (sharded chunks arrive pre-split by
            # shard_map), so the call is built per trace
            shapes = [tuple(t.shape) for t in args[:nsp]]
            if target.smem_table_bytes is not None:
                check_smem_tables(shapes, plan, target.smem_table_bytes)

            def views(refs):
                return tuple(RowMajor(r, shp) if len(shp) == 2 else r
                             for r, shp in zip(refs, shapes))

            def flat_spec(spec):
                im = getattr(spec, "index_map", None)
                if im is None:
                    return spec
                return dataclasses.replace(spec, index_map=lambda *a: im(
                    *a[:-nsp], *views(a[-nsp:])))

            def kernel_flat(*refs):
                wrapped(*views(refs[:nsp]), *refs[nsp:])

            outs = (type(out_specs)(flat_spec(o) for o in out_specs)
                    if isinstance(out_specs, (list, tuple))
                    else flat_spec(out_specs))
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=nsp,
                grid=plan.grid,
                in_specs=[flat_spec(sp) for sp in in_specs],
                out_specs=outs,
                scratch_shapes=list(scratch_shapes),
            )
            c = pl.pallas_call(
                kernel_flat, grid_spec=grid_spec, out_shape=out_shape,
                input_output_aliases=aliases, interpret=interp, **extra)
            return c(*(t.reshape(-1) for t in args[:nsp]), *args[nsp:])
    else:
        def call(*args):
            # table shapes are only known at call time (sharded chunks
            # arrive pre-split by shard_map); build the call lazily --
            # these closures only ever run under jit, so construction
            # cost is per-trace, not per-step.
            tspecs = [full_spec(t.shape) for t in args[:nsp]]
            c = pl.pallas_call(
                wrapped, grid=plan.grid,
                in_specs=tspecs + list(in_specs),
                out_specs=out_specs, out_shape=out_shape,
                input_output_aliases=aliases, interpret=interp, **extra)
            return c(*args)

    bound = plan.bound_prefetch()
    if bound is None:
        return _wrap(lambda *operands: call(*operands))
    return _wrap(lambda *operands: call(*bound, *operands))
