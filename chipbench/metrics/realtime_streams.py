"""Video seconds served per wall second: the stream-chunks the window
completed, times the video seconds of a chunk, over the window's wall
time on the host clock. A fleet of that many live cameras would keep up."""


def read(ctx):
    w = ctx.window
    return ctx.stream_chunks * w.chunk_s / w.seconds
