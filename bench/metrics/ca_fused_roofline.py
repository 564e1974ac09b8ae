"""The fused CA kernel (the Pallas kernel under ``ops.ca_run``'s jitted
``_ca_run_impl``): its share of the roofline over the traced window.
Counts: ``bench.counts.ca_fused`` per launch (the state read once and
written once; the rule's operations per member cell per step)."""
from bench import counts
from bench.trace import pallas_kernel

UNIT = "%"
ENTRY = "_ca_run_impl"


def read(ctx):
    k = ctx.work.get("kernel")
    if not k or k["entry"] != ENTRY:
        return None
    return counts.roofline_share(
        ops=k["ops"], nbytes=k["bytes"],
        seconds=ctx.trace.kernel_seconds(pallas_kernel(ENTRY)),
        peaks=ctx.peaks)
