"""Share of its roofline the fused codec kernel reaches: the least time a
chunk interval's encode could take (the larger of the separable
transforms' FLOPs over the bf16 peak and the frames read and written over
HBM bandwidth) over the kernel's device time per chunk interval."""
from chipbench import flops, trace_reduce

CAMERA = r"^jit__step\b"
KERNEL = r"mbcodec|_chunk_scores_kernel|_chunk_kernel"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    _, chunks = trace_reduce.module_ns(ctx.trace, CAMERA, ctx.lo, ctx.hi)
    ns, n = trace_reduce.op_ns(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if not (chunks and n):
        return None
    c = ctx.cfg
    args = (c["chunk_size"], c["height"], c["width"])
    least = ctx.n_streams * max(flops.codec_flops(*args) / ctx.peak.bf16_flops,
                                flops.codec_bytes(*args) / ctx.peak.hbm_bytes_s)
    return 100.0 * least * chunks / (ns * 1e-9)
