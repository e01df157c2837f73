"""Multi-process fleet runtime over ``jax.distributed``.

The stream mesh (PR 2) shards one *process's* devices; a deployed fleet is
many ingestion hosts with independent uplinks feeding shared server
capacity. This module is the thin runtime layer that turns N cooperating
processes into that fleet:

- :func:`init_from_env` joins the ``jax.distributed`` service from the
  ``FLEET_COORD`` / ``FLEET_NPROCS`` / ``FLEET_PROC_ID`` environment the
  launcher (``repro.launch.fleet``) sets — a CPU coordinator on
  ``127.0.0.1`` is enough, no TPU required.
- :class:`KVExchange` is the cross-host reduction primitive: a JSON
  object allgather over the coordinator's key-value store. The camera
  side of fleet serving is embarrassingly parallel (each host runs its
  own camera fleet step on its own local devices), so the *only*
  cross-host traffic is control-plane metadata — per-stream chunk
  accounting and autoscaler occupancy summaries — which is exactly what
  a KV allgather carries. No cross-process device collectives are
  needed, so the whole thing runs on hosts with no TPU and no gloo/mpi
  CPU collectives.
- :class:`LocalExchange` is the single-process fallback: ``allgather``
  of a 1-host fleet. ``exchange()`` picks the right one, so callers
  (``repro.serve.fleet.serve_fleet``) never branch on process count.

Keys are single-use (the coordinator KV store has no overwrite), so the
exchange stamps every round with a monotonically increasing counter;
hosts stay in lockstep because each ``allgather`` blocks until every
peer's value for that round arrives.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Any, List

import jax


#: environment contract with ``repro.launch.fleet`` (and any external
#: process manager: k8s pod env, mpirun wrapper, ...)
ENV_COORD = "FLEET_COORD"
ENV_NPROCS = "FLEET_NPROCS"
ENV_PROC_ID = "FLEET_PROC_ID"


def init_from_env() -> bool:
    """Join the ``jax.distributed`` service described by the launcher's
    environment. Returns False (single-process mode) when the env is not
    set, so library code can call this unconditionally. Must run before
    the first JAX backend touch in the worker process."""
    coord = os.environ.get(ENV_COORD)
    if not coord:
        return False
    num = int(os.environ[ENV_NPROCS])
    pid = int(os.environ[ENV_PROC_ID])
    jax.distributed.initialize(coord, num_processes=num, process_id=pid)
    return True


def is_distributed() -> bool:
    return jax.process_count() > 1


class LocalExchange:
    """Single-process stand-in for :class:`KVExchange`: one host, whose
    allgather is the identity. ``serve_fleet`` uses it to *simulate* a
    multi-host topology in one process (the default path — existing
    single-process callers never change)."""

    n_hosts = 1
    host = 0

    def __init__(self):
        self.failed = set()

    def live(self) -> List[int]:
        return [h for h in range(self.n_hosts) if h not in self.failed]

    def mark_failed(self, host: int) -> None:
        self.failed.add(int(host))

    def allgather(self, tag: str, obj: Any) -> List[Any]:
        # round-trip through JSON so the fallback has the same float /
        # tuple-vs-list semantics as the real cross-host exchange —
        # parity tests compare the two paths bit for bit
        return [json.loads(json.dumps(obj))]

    def tolerant_allgather(self, tag: str, obj: Any,
                           tolerate=(), timeout_s: float = 20.0
                           ) -> List[Any]:
        return self.allgather(tag, obj)

    def barrier(self, name: str = "sync") -> None:
        pass


class KVExchange:
    """Cross-host JSON allgather over the ``jax.distributed``
    coordinator's key-value store.

    Every host calls ``allgather(tag, obj)`` in the same order; call k
    publishes under ``fleetx/<tag>/<k>/<host>`` and blocks until all
    peers' round-k values arrive. JSON float serialization is exact
    (round-trippable repr), so gathered accounting stays bit-identical
    to the host that produced it.

    The round counter is *process-global* (shared by every instance),
    not per-instance: coordinator keys are single-use, so two exchanges
    created by two back-to-back ``serve_fleet`` calls must never reuse
    round numbers — and because every host runs the same program in the
    same order (the lockstep contract), the global counters stay aligned
    across hosts exactly as well as per-instance ones would within one
    call.
    """

    _rounds = itertools.count()    # process-global: keys are single-use
    _barrier_rounds = itertools.count()

    def __init__(self, timeout_s: float = 120.0):
        from jax._src.distributed import global_state

        client = getattr(global_state, "client", None)
        if client is None:
            raise RuntimeError(
                "KVExchange needs jax.distributed.initialize() first "
                "(repro.distributed.multihost.init_from_env, or the "
                "repro.launch.fleet launcher)")
        self._client = client
        self.timeout_ms = int(timeout_s * 1000)
        self.host = jax.process_index()
        self.n_hosts = jax.process_count()
        # hosts marked dead (explicitly or by a tolerant gather timing
        # out): all subsequent gathers skip them, so survivors stay in
        # lockstep with each other rather than blocking on a corpse
        self.failed = set()

    def live(self) -> List[int]:
        return [h for h in range(self.n_hosts) if h not in self.failed]

    def mark_failed(self, host: int) -> None:
        self.failed.add(int(host))

    def allgather(self, tag: str, obj: Any) -> List[Any]:
        base = f"fleetx/{tag}/{next(self._rounds)}"
        self._client.key_value_set(f"{base}/{self.host}", json.dumps(obj))
        return [json.loads(self._client.blocking_key_value_get(
            f"{base}/{h}", self.timeout_ms)) for h in self.live()]

    def tolerant_allgather(self, tag: str, obj: Any,
                           tolerate=(), timeout_s: float = 20.0
                           ) -> List[Any]:
        """Allgather that survives the death of any host in ``tolerate``:
        those hosts get a short per-host timeout instead of the exchange
        default, and a timeout marks the host failed (its value is
        omitted) rather than raising. Hosts not in ``tolerate`` keep the
        fail-loud default — an unexpected corpse is still a bug.

        Every live host must pass the same ``tolerate`` set (lockstep),
        so after the round all survivors agree on ``failed``."""
        tolerate = {int(h) for h in tolerate}
        base = f"fleetx/{tag}/{next(self._rounds)}"
        self._client.key_value_set(f"{base}/{self.host}", json.dumps(obj))
        out: List[Any] = []
        for h in self.live():
            ms = int(timeout_s * 1000) if h in tolerate else self.timeout_ms
            try:
                out.append(json.loads(self._client.blocking_key_value_get(
                    f"{base}/{h}", ms)))
            except Exception:  # XlaRuntimeError: DEADLINE_EXCEEDED
                if h not in tolerate:
                    raise
                self.mark_failed(h)
        return out

    def barrier(self, name: str = "sync") -> None:
        self._client.wait_at_barrier(
            f"fleetb/{name}/{next(self._barrier_rounds)}", self.timeout_ms)


def exchange(timeout_s: float = 120.0):
    """The right exchange for the current runtime: KV-backed when this
    process joined a ``jax.distributed`` fleet, local otherwise."""
    return KVExchange(timeout_s) if is_distributed() else LocalExchange()
