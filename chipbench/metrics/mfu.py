"""Whole-step model FLOP utilization: the model work per stream-chunk
(AccModel on the chunk head, every server-DNN pass over every frame, the
codec's separable transforms), times the stream-chunks the traced window
completed per second of its host-clock wall time, over the chips' bf16
peak."""
from chipbench import flops


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    rate = ctx.stream_chunks / ctx.window.seconds
    return 100.0 * flops.stream_chunk_flops(ctx.cfg) * rate / (
        ctx.chips * ctx.peak.bf16_flops)
