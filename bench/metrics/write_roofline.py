"""The map kernel (the Pallas kernel under ``ops.sierpinski_write``'s
jitted ``_write_impl``): its share of the roofline over the traced
window.  Counts: ``bench.counts.write`` per call (the stored array
written once)."""
from bench import counts
from bench.trace import pallas_kernel

UNIT = "%"
ENTRY = "_write_impl"


def read(ctx):
    k = ctx.work.get("kernel")
    if not k or k["entry"] != ENTRY:
        return None
    return counts.roofline_share(
        ops=k["ops"], nbytes=k["bytes"],
        seconds=ctx.trace.kernel_seconds(pallas_kernel(ENTRY)),
        peaks=ctx.peaks)
