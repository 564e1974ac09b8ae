"""Share of the traced window in which no operation ran on the device, in
the serving cells (those that report ``output_tokens_per_s``)."""
UNIT = "%"


def read(ctx):
    if "output_tokens_per_s" not in ctx.end_to_end \
            or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
