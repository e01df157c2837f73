"""Logical-axis sharding rules.

Model code annotates tensors with *logical* axes; this module resolves them
against the active mesh with divisibility checks (GSPMD rejects uneven
sharding of explicit dims — verified empirically), falling back to
replication when a dim does not divide.

Logical axes
------------
``tp``    tensor-parallel axis -> mesh "model"
``fsdp``  ZeRO-3 style parameter sharding -> mesh "data" (never "pod": the
          cross-pod links are the slow tier, parameters are replicated across
          pods and gradients crossing pods can be compressed instead)
``dp``    batch -> mesh ("pod","data")
``ep``    expert -> mesh "model"
``seq_all`` sequence sharded over every mesh axis (long-context KV caches
          with batch=1)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def host_local_devices():
    """The devices fleet-serving meshes may use in this process.

    Under ``jax.distributed`` multi-process serving each host runs its
    *own* camera fleet programs on its *own* devices (the stream axis has
    no cross-stream collectives, so a global SPMD mesh would only force
    global-array plumbing for zero win) — so fleet meshes are built over
    ``jax.local_devices()``. Single-process, local == global and nothing
    changes for existing callers.
    """
    return jax.local_devices() if jax.process_count() > 1 else jax.devices()


def assert_addressable_mesh(mesh: Mesh, what: str) -> None:
    """Loud error when a fleet mesh names devices this process cannot
    address (another host's). Fleet camera/server steps are host-local
    by design; silently lowering over a global mesh would hang or
    mis-shard. Multi-host serving goes through
    ``repro.serve.fleet.serve_fleet`` instead."""
    pid = jax.process_index()
    remote = [d for d in np.asarray(mesh.devices).flat
              if getattr(d, "process_index", pid) != pid]
    if remote:
        raise ValueError(
            f"{what} is host-local but the mesh names "
            f"{len(remote)} device(s) owned by other processes "
            f"(process {pid} of {jax.process_count()}); build fleet meshes "
            f"over jax.local_devices() (distributed.mesh helpers do) and "
            f"use repro.serve.fleet.serve_fleet for multi-host serving")


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the one call the
    sharded fleet-serving path (and the distributed tests) go through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class Rules:
    mesh: Mesh
    fsdp: bool = False  # shard params over the data axis as well (ZeRO-3)
    manual_pod: bool = False  # "pod" handled manually (shard_map) — drop it
    # from dp so inner GSPMD constraints never name it

    # ---- mesh introspection -------------------------------------------------
    @property
    def axis_sizes(self) -> dict:
        return dict(self.mesh.shape)

    @property
    def tp(self) -> int:
        return int(self.axis_sizes.get("model", 1))

    @property
    def dp_axes(self) -> tuple:
        names = ("data",) if self.manual_pod else ("pod", "data")
        return tuple(a for a in names if a in self.axis_sizes)

    @property
    def dp(self) -> int:
        return int(np.prod([self.axis_sizes[a] for a in self.dp_axes])) if self.dp_axes else 1

    @property
    def fsdp_axes(self) -> tuple:
        return ("data",) if (self.fsdp and "data" in self.axis_sizes) else ()

    @property
    def fsdp_size(self) -> int:
        return int(self.axis_sizes.get("data", 1)) if self.fsdp_axes else 1

    @property
    def all_axes(self) -> tuple:
        return tuple(self.mesh.axis_names)

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.axis_sizes.values())))

    # ---- logical resolution -------------------------------------------------
    def _resolve(self, logical: Optional[str], size: Optional[int]):
        if logical is None:
            return None
        if logical == "tp":
            axes, n = ("model",), self.tp
        elif logical == "fsdp":
            axes, n = self.fsdp_axes, self.fsdp_size
        elif logical == "dp":
            axes, n = self.dp_axes, self.dp
        elif logical == "ep":
            axes, n = ("model",), self.tp
        elif logical == "seq_all":
            axes, n = self.all_axes, self.n_devices
        else:
            raise ValueError(f"unknown logical axis {logical!r}")
        if not axes or n <= 1:
            return None
        if size is not None and size % n != 0:
            return None  # uneven -> replicate (policy fallback happens above us)
        if len(axes) == 1:
            return axes[0]
        return axes

    def spec(self, *dims) -> P:
        """Each dim is ``None`` | ``logical`` | ``(logical, size)``.

        Passing the size enables the divisibility fallback; bare names skip it
        (used for activation constraints where GSPMD tolerates propagation).
        """
        out = []
        for d in dims:
            if d is None:
                out.append(None)
            elif isinstance(d, tuple):
                out.append(self._resolve(d[0], d[1]))
            else:
                out.append(self._resolve(d, None))
        return P(*out)

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, *dims):
        """with_sharding_constraint against logical dims (size-checked)."""
        sized = []
        for i, d in enumerate(dims):
            if d is None or isinstance(d, tuple):
                sized.append(d)
            else:
                sized.append((d, x.shape[i]))
        return jax.lax.with_sharding_constraint(x, self.named(self.spec(*sized)))

    # ---- divisibility probes (used by attention policy selection) ----------
    def divides_tp(self, n: int) -> bool:
        return n % self.tp == 0

    def divides_dp(self, n: int) -> bool:
        return n % self.dp == 0


def local_rules() -> Rules:
    """Rules for a single-device mesh (unit tests / smoke tests)."""
    from repro.distributed.mesh import make_local_mesh

    return Rules(make_local_mesh())


def prepend(spec: P, *axes) -> P:
    """Prepend dims to a PartitionSpec (stacked-by-scan parameters)."""
    return P(*axes, *tuple(spec))


def tree_prepend(specs, *axes):
    return jax.tree_util.tree_map(
        lambda s: prepend(s, *axes), specs, is_leaf=lambda s: isinstance(s, P)
    )


def named_tree(rules: Rules, specs):
    return jax.tree_util.tree_map(
        lambda s: rules.named(s), specs, is_leaf=lambda s: isinstance(s, P)
    )
