"""The program's spans, on the profiler's clock.

:func:`span` is :class:`jax.profiler.TraceAnnotation`: a host span that
the profiler records beside the device's planes while a session runs
(``jax.profiler.start_trace`` or ``jax.profiler.trace``).  With no
session running a span costs about a microsecond and records nothing.
Its counters are integers or short strings that the host already holds:
a span never reads a device value, and a counter known only once the
work is done is added with ``set_metadata`` on the open span.

Spans, parents first, and what their counters mean:

``serve.step`` -- one :meth:`PagedServer.step <repro.launch.serve.PagedServer.step>`
    ``step``: steps this server has run before this one; ``active``:
    slots decoding when the step begins; ``decode_grid_steps``: grid
    steps the block-indexed paged decode kernels run in the decode step
    dispatched (the fused-KV kernel's grid times the attention layers
    plus the latent kernel's times the latent layers; 0 on the ``xla``
    rung); at its end ``preempted``:
    requests preempted to make room; ``pages_in_use``, ``free_pages``,
    ``live_tokens``, ``alloc_tokens``: the pool after the step; under a
    held-experts share (``ModelConfig.experts_held``) also
    ``moe_routes_held``, the step's routes that landed on held experts
    (every slot, every MoE layer), and ``moe_max_load``, the most routes
    one held expert of one layer computed.

    ``serve.grow``: page growth and preemption.  ``serve.inputs``: the
    host arrays of tokens, positions and the active mask, and their
    uploads with the page table.  ``guard.call`` (below) around the
    decode call.  ``serve.release``: the previous pools dropped.
    ``serve.sample`` (``slots``): the logits' copy to the host (then,
    under a held-experts share, the decode step's per-layer route
    counts) and each slot's sampling.  ``serve.table``: finished requests freed,
    ``verify_page_table`` and the pool's statistics.

``serve.admit`` -- one admission by ``PagedServer._admit_one``
    ``rid``: the request; ``prompt_tokens``: tokens prefilled (prompt
    and those already generated); ``pages``: pages allocated;
    ``replayed``: generated tokens replayed after a preemption.

    ``guard.call`` around the prefill, ``serve.scatter`` (the prefill's
    KV written into its pages), ``serve.sample`` and ``serve.table``.

``guard.call`` -- one :class:`~repro.runtime.guard.GuardedCall`
    ``site``: the call site (``serve.prefill``, ``serve.decode``, ...).

    ``guard.run`` (``attempt``, from 1): the wrapped call and its
    ``block_until_ready``, so dispatch and the wait on the device.
    ``guard.validate``: the output's validators; where
    :func:`~repro.runtime.guard.validate_finite` is one, ``leaves``,
    ``bytes_screened`` (every floating leaf, bfloat16 included),
    ``bytes_to_host`` (what the screen copies from the device: one flag
    per device leaf, no leaf itself) and ``screened_on_device`` (the
    leaves the device screens).

``kernels.ca_run`` / ``kernels.sierpinski_write`` -- one call of the
λ-kernel entry
    ``grid_mode``, ``storage``; for ``ca_run`` also ``steps``, ``fuse``
    (as executed) and ``launches``; ``grid_steps``: grid steps per
    launch where the domain is given and the lowering launches one
    step per member block (``closed_form``, ``prefetch_lut``, ``mma``).

    ``kernels.ca_run.schedule`` / ``kernels.sierpinski_write.schedule``:
    the tune-cache lookup of ``"auto"`` knobs.
    ``kernels.ca_run.dispatch`` / ``kernels.sierpinski_write.dispatch``:
    the jitted call (asynchronous: dispatch, not the device's work).

No name here is one of the benchmark's own spans (``bench.window``,
``ops.ca_run``, ``ops.sierpinski_write``, ``PagedServer.step``,
``PagedServer._admit_one``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **counters) -> TraceAnnotation:
    """A host span ``name`` with ``counters`` (integers or short
    strings), to be used as a context manager."""
    return TraceAnnotation(name, **counters)
