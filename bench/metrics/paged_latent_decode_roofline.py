"""The paged latent-attention decode kernel (``paged_latent_decode``,
launched under the jitted ``_latent_decode_impl``): its share of the
roofline over the traced window.  Counts:
``bench.counts_mla.paged_latent_decode`` of each decode step, from the
live contexts of its slots (unpadded latent rows)."""
from bench import counts, counts_mla
from bench.trace import instruction

UNIT = "%"
KERNEL = "paged_latent_decode"
ENTRY = "_latent_decode_impl"


def is_kernel(e, module: str) -> bool:
    """The kernel's custom call: its XLA instruction carries the
    kernel's name, or its metadata the jitted entry's."""
    text = e.name + " " + e.meta
    if "tpu_custom_call" not in text and "pallas_call" not in text:
        return False
    return instruction(e).startswith(KERNEL) or KERNEL in e.meta \
        or ENTRY in e.meta or module.startswith(f"jit_{ENTRY}(")


def read(ctx):
    steps, dm = ctx.work.get("mla_contexts"), ctx.work.get("mla_dims")
    if not steps or not dm:
        return None
    ops = nbytes = 0
    for contexts in steps:
        c = counts_mla.paged_latent_decode(contexts=contexts, dm=dm)
        ops += c["ops"]
        nbytes += c["bytes"]
    return counts.roofline_share(
        ops=ops, nbytes=nbytes, seconds=ctx.trace.kernel_seconds(is_kernel),
        peaks=ctx.peaks)
