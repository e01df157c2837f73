"""Share of its roofline the server DNN's step reaches: the model FLOPs
of one ``jit__server`` execution (the server module's ``frame_flops``
for every frame of a chunk of the chip's share of the streams) over one
chip's bf16 peak, over that execution's mean device time. FCN-ResNet101's
convolutions at 720p are bound by compute, so the FLOPs over the peak
are the least time the step could take."""
from chipbench import servers, trace_reduce

SERVER = r"^jit__server\b"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    ns, n = trace_reduce.module_ns(ctx.trace, SERVER, ctx.lo, ctx.hi)
    if not n:
        return None
    c = ctx.cfg
    frames = c["chunk_size"] * ctx.n_streams // ctx.chips
    least = servers.for_config(c).frame_flops(c) * frames \
        / ctx.peak.bf16_flops
    return 100.0 * least / (ns / n * 1e-9)
