"""Device time of the server fleet step (``jit__server``, every pass) and
of the device accuracy reduction (``jit__acc``, where it runs), per
camera-step execution, i.e. per chunk interval."""
from chipbench import trace_reduce

CAMERA = r"^jit__step\b"
SERVER = r"^jit__(server|acc)\b"


def read(ctx):
    if ctx.trace is None:
        return None
    _, chunks = trace_reduce.module_ns(ctx.trace, CAMERA, ctx.lo, ctx.hi)
    ns, n = trace_reduce.module_ns(ctx.trace, SERVER, ctx.lo, ctx.hi)
    return ns / chunks * 1e-6 if chunks and n else None
