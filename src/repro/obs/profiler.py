"""``jax.profiler`` wiring: device traces from the same run as the span
timeline.

The span tracer (:mod:`repro.obs.trace`) explains host-visible time;
``jax.profiler`` explains what the device did inside a step. Launch
entry points (``repro.launch.serve``, ``repro.launch.fleet``) accept
``--profile DIR`` and wrap their serving region in
:func:`profile_region`, so one run yields both views with a shared wall
clock — open the Chrome trace in Perfetto beside the device trace in
TensorBoard's profile plugin (or Perfetto's XPlane support).

Multi-process fleets give each worker its own subdirectory
(``DIR/host<k>``); ``jax.profiler.start_trace`` is per-process.
A requested trace that cannot start or stop raises: a run asked to
record the device must not exit 0 without the recording.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

from repro.obs import trace as _trace


@contextlib.contextmanager
def profile_region(profile_dir: Optional[str],
                   host: Optional[int] = None) -> Iterator[bool]:
    """Run the enclosed block under ``jax.profiler`` tracing into
    ``profile_dir`` (no-op context when ``profile_dir`` is falsy).
    Yields True when the profiler started; raises when it cannot. Start/stop land as
    instants on the span timeline so the profiled window is visible in
    the merged Chrome trace."""
    if not profile_dir:
        yield False
        return
    import jax

    target = profile_dir if host is None \
        else os.path.join(profile_dir, f"host{host}")
    os.makedirs(target, exist_ok=True)
    jax.profiler.start_trace(target)
    _trace.instant("profiler_start", stage="events", dir=target,
                   active=True)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
        _trace.instant("profiler_stop", stage="events", dir=target)
