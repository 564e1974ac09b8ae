"""Kernel emission: the ``BackendTarget`` descriptor plus the one place
in the repo that constructs Pallas grid specs.

Every kernel has one structure, the TPU (Mosaic) one: operand placement
happens in ``BlockSpec`` index maps, which may read host-built decode
tables through scalar prefetch; run-time scalars ride SMEM refs; the
grid is sequential, so revisited output blocks accumulate across steps
and online-softmax state lives in VMEM scratch.  It runs on two targets:

``tpu``
    Compiled through Mosaic for the chip.

``tpu-interpret``
    The same structure executed by the Pallas interpreter, which is how
    the CPU runs (and tests) every kernel.

Selection order for the default target: an explicit ``backend=``
argument > :func:`set_default` (the ``--backend`` flag of serve/train)
> the jax platform (tpu -> ``tpu``, anything else ->
``tpu-interpret``).  A native target named explicitly stays native on
every platform: off its chip it fails to lower instead of being
emulated unseen.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_OVERRIDE: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class BackendTarget:
    """Descriptor of one kernel-emission target:

    interpret:           run the kernels under the Pallas interpreter.
    pipeline_stages:     maximum useful staged-copy depth for
                         software-pipelined streaming loops (the DMA
                         double buffers, 2).  ``resolve_stages`` clamps
                         every request to it.
    smem_table_bytes:    SMEM the scalar-prefetch tables of one launch
                         may fill together, or None where nothing bounds
                         them (the interpreter).  Tables over it are
                         refused when the launch is traced
                         (:func:`check_smem_tables`).
    """

    name: str
    interpret: bool
    pipeline_stages: int
    smem_table_bytes: Optional[int]

    def emulated(self) -> "BackendTarget":
        """This target under the interpreter."""
        return TPU_INTERPRET

    def native(self) -> "BackendTarget":
        return TPU

    def resolve_stages(self, num_stages: Optional[int]) -> int:
        """Clamp a requested pipeline depth to what this target can
        stage.  ``None`` / ``"auto"`` and anything <= 1 mean the
        synchronous path; depths beyond :attr:`pipeline_stages` clamp
        down rather than error so a tune-cache entry asking for more
        stays usable."""
        if num_stages is None or num_stages == "auto":
            return 1
        return max(1, min(int(num_stages), self.pipeline_stages))


#: SMEM a kernel's scalar-prefetch tables may fill together: the 1 MiB
#: of a TPU v5e core, less 16 KiB kept for the compiler's own scalars.
#: Each table is placed in whole 4 KiB pages.
SMEM_TABLE_BYTES = (1 << 20) - (16 << 10)
_SMEM_PAGE = 4096

# the chip's SMEM budget; the interpreter keeps tables in host memory
TPU = BackendTarget(name="tpu", interpret=False, pipeline_stages=2,
                    smem_table_bytes=SMEM_TABLE_BYTES)
TPU_INTERPRET = BackendTarget(name="tpu-interpret", interpret=True,
                              pipeline_stages=2, smem_table_bytes=None)

TARGETS = {t.name: t for t in (TPU, TPU_INTERPRET)}
_ALIASES = {"mosaic": "tpu", "interpret": "tpu-interpret"}


def platform_default() -> BackendTarget:
    """The target the bare jax platform implies, ignoring
    :func:`set_default`.  This is the reference point for *persisted*
    qualification (tune-cache keys): a process whose default was steered
    away from the platform must stamp its entries, or another process
    with a different default would read them as its own."""
    return TPU if jax.default_backend() == "tpu" else TPU_INTERPRET


def set_default(name: Optional[str]) -> None:
    """Process-wide default target override (the ``--backend`` flag of
    serve/train); ``None`` restores platform selection."""
    global _OVERRIDE
    if name is not None:
        resolve(name)  # validate eagerly
    _OVERRIDE = name


def resolve(spec=None, interpret: Optional[bool] = None) -> BackendTarget:
    """Normalize a backend spec to a :class:`BackendTarget`.

    spec: a target (returned as given), a name ("tpu" |
    "tpu-interpret" | "interpret"), or None (defaulting rules in the
    module docstring).  ``interpret=True`` forces emulation;
    ``interpret=False`` pins the native target.  With ``interpret``
    unset the named target is kept as it is: a native target is never
    emulated behind the caller's back, so a compile rehearsal off the
    chip reaches the real compiler.
    """
    if isinstance(spec, BackendTarget):
        target = spec
    else:
        if spec is None:
            spec = _OVERRIDE
        if spec is None:
            target = platform_default()
        else:
            name = _ALIASES.get(spec, spec)
            if name not in TARGETS:
                raise ValueError(
                    f"unknown backend {spec!r}; expected one of "
                    f"{tuple(TARGETS)} or {tuple(_ALIASES)}")
            target = TARGETS[name]
    if interpret is True:
        return target.emulated()
    if interpret is False:
        return target.native()
    return target


def stream_tiles(src_ref, bufs_ref, sems, *, srcs_for, lin, total,
                 stages):
    """One sequential-grid step of software-pipelined tile streaming
    (an async-copy double/multi buffer).

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
         interpret: Optional[bool] = None, name: Optional[str] = None,
         **kwargs) -> Callable:
    """Build the ``pl.pallas_call`` for ``plan`` on its target.

    ``kernel(coords, *refs)`` is lowering-agnostic at the signature
    level; the wrapper injects the decoded
    :class:`~repro.core.plan.BlockCoords`.  The plan's decode tables
    (``plan.num_scalar_prefetch`` of them) ride a
    ``PrefetchScalarGridSpec``, readable from index maps and the kernel
    prologue.

    ``input_output_aliases`` is keyed on the *array* operands (tables
    excluded); the emitter shifts it.  When :meth:`plan.bound_prefetch`
    returns tables the returned callable takes just the array operands;
    when it returns ``None`` the caller passes the tables first
    (sharded plans, whose tables are per-device ``shard_map``
    operands).

    ``name`` names the kernel: on the TPU it becomes the Mosaic custom
    call's ``kernel_name`` and the name of its XLA instruction, which
    the profiler's trace shows.
    """
    target = plan.target
    interp = target.interpret if interpret is None else interpret
    aliases = {int(i): int(o)
               for i, o in (input_output_aliases or {}).items()}
    nsp = plan.num_scalar_prefetch
    extra = dict(kwargs, name=name)

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

    # operand indices count the tables as inputs 0..nsp
    aliases = {i + nsp: o for i, o in aliases.items()}

    def call(*args):
        # tables ride flat (see RowMajor); their shapes are known only
        # at call time (sharded chunks arrive pre-split by shard_map),
        # so the call is built per trace
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

    bound = plan.bound_prefetch()
    if bound is None:
        return _wrap(lambda *operands: call(*operands))
    return _wrap(lambda *operands: call(*bound, *operands))
