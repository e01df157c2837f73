"""Set-up time: process start to the opening of the measured window
(weights, frames, D(H) references, warm-up calls, and any compile)."""


def read(ctx):
    return ctx.setup_s
