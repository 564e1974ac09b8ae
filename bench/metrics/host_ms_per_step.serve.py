"""Host time per ``PagedServer.step``: the benchmark's span around each
step in the traced window, less the device-busy time inside it, mean
over the steps."""
UNIT = "ms"


def read(ctx):
    spans = ctx.trace.spans("PagedServer.step")
    if "output_tokens_per_s" not in ctx.end_to_end or not spans:
        return None
    host = [(b - a) * 1e-9 - ctx.trace.busy_between(a, b) for a, b in spans]
    return 1e3 * sum(host) / len(host)
