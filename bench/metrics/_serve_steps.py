"""The program's spans inside each ``serve.step`` of the traced window
(``repro.runtime.trace`` names them), for the serving cells' host-path
metrics.  A program without those spans yields no steps, and its
metrics are left out of the line."""


def steps(ctx):
    """(start, end) ns of the ``serve.step`` spans wholly inside the
    window, in the cells that report ``output_tokens_per_s``; empty
    elsewhere."""
    if "output_tokens_per_s" not in ctx.end_to_end:
        return []
    t = ctx.trace
    return [(a, b) for a, b in t.spans("serve.step")
            if a >= t.t0 and b <= t.t1]


def inside_ms(ctx, spans, name):
    """Per step of ``spans``: the ms that the spans ``name`` lying
    wholly inside it take."""
    kids = ctx.trace.spans(name)
    return [1e-6 * sum(b - a for a, b in kids if a >= sa and b <= sb)
            for sa, sb in spans]


def mean(values):
    return sum(values) / len(values) if values else None
