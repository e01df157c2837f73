"""The system under test, built for one cell from the seed.

Set-up draws the weights (one device call, from the configuration's
``weight_seed``) and, from the run's seed, a pool of distinct chunks per
stream (one device call per chunk), fetches the pool to host memory,
and builds ``MultiStreamEngine`` with ``EngineConfig(impl="fused",
mesh="auto")`` and the configuration's accounting mode. A window serves
the pool's chunks in turn: chunk ``ci`` of every stream is pool chunk
``ci % P``, read from host memory and ingested by the engine as a
deployment ingests its cameras' chunks.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import numpy as np

from chipbench import nets, scene


class ChunkPool:
    """A ``(N, n_chunks * T, H, W, 3)`` video for ``MultiStreamEngine.run``
    whose chunk ``ci`` is ``pool[(first + ci) % P]``. ``run`` reads only
    ``.shape`` and whole-chunk slices ``[:, s:s + T]``; each slice is one
    contiguous host array, no copy."""

    def __init__(self, pool: np.ndarray, n_chunks: int, first: int = 0):
        self.pool = pool  # (P, N, T, H, W, 3)
        self.P, N, self.T = pool.shape[:3]
        self.first = first
        self.shape = (N, n_chunks * self.T) + pool.shape[3:]

    def chunk_index(self, ci: int) -> int:
        return (self.first + ci) % self.P

    def __getitem__(self, key):
        streams, frames = key
        if streams != slice(None) or frames.step not in (None, 1):
            raise IndexError(f"ChunkPool serves whole chunks, got {key}")
        start = frames.start or 0
        if start % self.T or frames.stop - start != self.T \
                or frames.stop > self.shape[1]:
            raise IndexError(f"ChunkPool serves whole chunks, got {key}")
        return self.pool[self.chunk_index(start // self.T)]


@dataclasses.dataclass
class System:
    cfg: dict
    n_streams: int
    acc_params: dict
    dnn_params: dict
    pool: np.ndarray             # (P, N, T, H, W, 3) float32, host
    engine: object               # MultiStreamEngine
    pool_refs: Optional[List]    # [stream][pool chunk] D(H) dicts, host

    def video(self, n_chunks: int, first: int = 0) -> ChunkPool:
        return ChunkPool(self.pool, n_chunks, first)

    def refs(self, video: ChunkPool):
        """refs[stream][ci] for ``run``, or None where D(H) is computed in
        the loop."""
        if self.pool_refs is None:
            return None
        n = video.shape[1] // video.T
        return [[row[video.chunk_index(ci)] for ci in range(n)]
                for row in self.pool_refs]


def build(cfg: dict, seed: int, n_streams: int, pool_chunks: int) -> System:
    from repro.core.accmodel import AccModel
    from repro.core.aggregate import AggregateConfig
    from repro.core.pipeline import make_reference
    from repro.core.quality import QualityConfig
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.vision.dnn import FinalDNN, detection_keep_heat

    key = scene.seed_key(seed)
    task, T = cfg["task"], cfg["chunk_size"]
    H, W = cfg["height"], cfg["width"]
    # the program compiles its weights into its programs as constants, so
    # the configuration fixes them; the run's seed draws the video
    acc, dnn = nets.make_weights(scene.seed_key(cfg["weight_seed"]), task,
                                 cfg["accmodel_width"], cfg["dnn_width"])
    pool = np.empty((pool_chunks, n_streams, T, H, W, 3), np.float32)
    for s in range(n_streams):
        skey = jax.random.fold_in(key, s)
        for p in range(pool_chunks):
            pool[p, s] = np.asarray(scene.dashcam_chunk(skey, p * T, T, H, W))
    final_dnn = FinalDNN(task, dnn)
    config = EngineConfig(
        qcfg=QualityConfig(**cfg["quality"]), chunk_size=T,
        impl=cfg["impl"], mesh=cfg["mesh"], fps=cfg["fps"],
        detail=cfg["detail"], device_reduce=cfg["device_reduce"],
        aggregate=AggregateConfig(**cfg["aggregate"]) if "aggregate" in cfg
        else None)
    engine = MultiStreamEngine(final_dnn, AccModel(acc), config=config)
    pool_refs = None
    if cfg["refs"] == "precomputed":
        keep = jax.jit(detection_keep_heat) if task == "detection" else None
        pool_refs = []
        for s in range(n_streams):
            row = make_reference(pool[:, s].reshape((-1, H, W, 3)),
                                 final_dnn, qp_hi=cfg["ref_qp"],
                                 chunk_size=T)
            if keep is not None:
                row = [dict(r, keep=keep(r)) for r in row]
            pool_refs.append([{k: np.asarray(v) for k, v in r.items()}
                              for r in row])
    return System(cfg, n_streams, acc, dnn, pool, engine, pool_refs)
