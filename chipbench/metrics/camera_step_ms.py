"""Device time per execution of the camera fleet step (AccModel, QP
assignment and the fused codec kernel), the program ``jit__step`` in the
trace."""
from chipbench import trace_reduce

PROGRAM = r"^jit__step\b"


def read(ctx):
    if ctx.trace is None:
        return None
    ns, n = trace_reduce.module_ns(ctx.trace, PROGRAM, ctx.lo, ctx.hi)
    return ns / n * 1e-6 if n else None
