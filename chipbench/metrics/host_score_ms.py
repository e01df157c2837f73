"""Mean host time per chunk interval of the engine's scoring and uplink
accounting stage, from the program's own ``FleetTiming.host_s`` span."""
import numpy as np


def read(ctx):
    host = ctx.window.host_s()
    return float(np.mean(host)) * 1e3 if host else None
