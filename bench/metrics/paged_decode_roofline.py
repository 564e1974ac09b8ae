"""The paged flash-decode kernel (the Pallas kernel under the jitted
``_paged_impl``): its share of the roofline over the traced window.
Counts: ``bench.counts.paged_decode`` of each decode step, from the live
contexts of its active slots (unpadded head size)."""
from bench import counts
from bench.trace import pallas_kernel

UNIT = "%"
ENTRY = "_paged_impl"


def read(ctx):
    steps, dm = ctx.work.get("decode_contexts"), ctx.work.get("dims")
    if not steps or not dm:
        return None
    ops = nbytes = 0
    for contexts in steps:
        c = counts.paged_decode(contexts=contexts, layers=dm["layers"],
                                heads=dm["heads"], kv_heads=dm["kv_heads"],
                                head_dim=dm["head_dim"])
        ops += c["ops"]
        nbytes += c["bytes"]
    return counts.roofline_share(
        ops=ops, nbytes=nbytes,
        seconds=ctx.trace.kernel_seconds(pallas_kernel(ENTRY)),
        peaks=ctx.peaks)
