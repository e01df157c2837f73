"""Serving steps: prefill and single-token decode (the dry-run's serve_step),
plus the camera-fleet step for vmap-batched multi-stream video serving.

``decode_step`` is what the decode_32k / long_500k cells lower: one new token
against a seq_len KV cache. The KV cache is sequence-sharded over the model
axis (batch over data), with GSPMD combining the partial softmax — the
flash-decoding schedule expressed in pjit.

``make_camera_fleet_step`` is the video analogue: the entire camera side of
N concurrent AccMPEG streams — AccModel scoring, QP-map assignment, and the
RoI chunk encode — lowered as one jitted XLA program with the stream axis
leading, so one dispatch serves a fleet of cameras per chunk interval.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Rules, shard_map


def make_camera_fleet_step(accmodel, qcfg, impl: str = "fast",
                           mesh: Mesh = None, knobs: bool = False,
                           mask: bool = False):
    """Build the fused per-chunk camera step for N streams.

    Returns ``step(chunks)`` with ``chunks (N, T, H, W, C)`` ->
    ``(decoded (N, T, H, W, C), bytes (N, T), scores (N, mb_h, mb_w))``.

    Frame sampling is the paper's k = chunk_size: AccModel runs on each
    stream's chunk head only, and the resulting per-stream QP map is reused
    for the whole chunk. ``impl`` selects the chunk encoder from the
    ``codec.CHUNK_ENCODERS`` registry — "fast" (coefficient-space scan, the
    serving default), "exact" (bit-stable reference), "fast_exact"
    (clip-corrected fast scan), "pallas" (fused mbcodec tile on TPU, jnp
    tile elsewhere), or "fused" / "fused_exact" (the chunk-fused camera
    fast-path: on TPU the step skips the materialized QP map entirely and
    hands the dilated score map + (alpha, qp_hi, qp_lo) knob triple to the
    VMEM-resident chunk kernel; "fused_exact" is bit-comparable to
    "exact").

    ``mesh``: a 1-D ``"stream"`` mesh (``distributed.mesh.make_stream_mesh``)
    shards the fleet axis via shard_map — each device traces the identical
    per-shard program on its N/n_shards streams (the camera side has no
    cross-stream collectives), so one host serves hundreds of cameras.
    N must divide the mesh width; ``mesh=None`` keeps the single-device
    vmap lowering.

    ``knobs=True`` builds the rate-controlled variant ``step(chunks,
    knob_array)``: alpha/qp_hi/qp_lo/drop_thresh arrive as a traced array
    (``control.controller.ControlKnobs.as_array``) instead of baked
    ``qcfg`` constants, so the fleet controller can move them every chunk
    without retriggering compilation (only ``qcfg.gamma`` stays static —
    it shapes the dilation window). Frames whose change feature falls
    below the drop threshold are replaced by the previous kept frame
    before encoding — the same static-shape soft drop as the
    single-stream ``ControlledAccMPEGPolicy``, vmapped over streams. The
    knob array is replicated across the stream mesh (every camera shares
    the fleet's uplink, so one knob set governs the fleet).

    ``mask=True`` builds the admission-controlled variant ``step(chunks,
    active[, knob_array])`` taking a traced ``(N,)`` lane mask
    (``control.autoscaler.AdmissionPlan.active``): padded idle lanes run
    the identical per-lane program (so every padded fleet shape is ONE
    compiled program regardless of which lanes are real) but their
    reported bytes are zeroed *inside* the program — downstream uplink
    and accuracy accounting can never be polluted by a padding lane. The
    mask rides as data, so membership churn at a fixed padded shape
    costs zero recompiles.
    """
    from repro.codec.codec import CHUNK_ENCODERS
    from repro.core.accmodel import accmodel_apply
    from repro.core.quality import (dilate_scores,
                                    qp_maps_from_knobs_batched,
                                    qp_maps_from_scores_batched)
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.engine.policies import soft_drop_previous

    if mesh is not None:  # loud, not a hang: fleet steps are host-local
        assert_addressable_mesh(mesh, "make_camera_fleet_step")

    params = accmodel.params
    enc = CHUNK_ENCODERS.resolve(impl)  # also validates impl early (loud)
    # fused backends take the scores path: the dilated score map + the
    # (alpha, qp_hi, qp_lo) triple go straight into the chunk kernel,
    # which assigns the two-level QP in-register (dilate_scores >= alpha
    # == dilate-then-select) — scoring, QP assignment, and the RoI
    # encode fuse into one program with no HBM-resident QP map
    fused_scores = impl in ("fused", "fused_exact")
    if fused_scores:
        from repro.kernels.mbcodec.ops import encode_chunk_fused_scores
        enc_scores = functools.partial(encode_chunk_fused_scores,
                                       clip_refs=(impl == "fused_exact"))

    def _encode(chunks, qmaps, scores, active=None):
        if fused_scores:
            pooled, ktriple = qmaps  # scores path: no materialized QP map
            decoded, pbytes = jax.vmap(
                lambda c, p: enc_scores(c, p, ktriple))(chunks, pooled)
        else:
            decoded, pbytes = jax.vmap(enc)(chunks, qmaps)
        if active is not None:  # zero padded lanes' wire bytes in-program
            lane = active.astype(pbytes.dtype)
            pbytes = pbytes * lane.reshape((-1,) + (1,) * (pbytes.ndim - 1))
        return decoded, pbytes, scores

    def _score_qmaps(chunks, knob_arr=None):
        scores = jax.nn.sigmoid(accmodel_apply(params, chunks[:, 0]))
        if knob_arr is not None:
            chunks = jax.vmap(
                lambda c: soft_drop_previous(c, knob_arr[3])[0])(chunks)
        if fused_scores:
            pooled = dilate_scores(scores, qcfg.gamma)
            ktriple = knob_arr[:3] if knob_arr is not None else jnp.array(
                [qcfg.alpha, float(qcfg.qp_hi), float(qcfg.qp_lo)],
                jnp.float32)
            return chunks, (pooled, ktriple), scores
        if knob_arr is None:
            qmaps, _ = qp_maps_from_scores_batched(scores, qcfg)
            return chunks, qmaps, scores
        qmaps, _ = qp_maps_from_knobs_batched(scores, knob_arr, qcfg.gamma)
        return chunks, qmaps, scores

    def _step(chunks):
        return _encode(*_score_qmaps(chunks))

    def _step_knobs(chunks, knob_arr):
        return _encode(*_score_qmaps(chunks, knob_arr))

    def _step_mask(chunks, active):
        return _encode(*_score_qmaps(chunks), active=active)

    def _step_mask_knobs(chunks, active, knob_arr):
        return _encode(*_score_qmaps(chunks, knob_arr), active=active)

    if mask:
        fn = _step_mask_knobs if knobs else _step_mask
    else:
        fn = _step_knobs if knobs else _step
    if mesh is None:
        return jax.jit(fn)
    spec = P(STREAM_AXIS)
    in_specs = (spec,) + ((spec,) if mask else ()) + ((P(),) if knobs else ())
    if len(in_specs) == 1:
        in_specs = spec
    sharded = shard_map(fn, mesh, in_specs=in_specs,
                        out_specs=(spec, spec, spec))
    return jax.jit(sharded)


def stream_sharding(mesh: Mesh) -> NamedSharding:
    """Stream-major input sharding for fleet batches (leading axis)."""
    from repro.distributed.mesh import STREAM_AXIS

    return NamedSharding(mesh, P(STREAM_AXIS))


class BoundStep:
    """A jitted step whose leading arguments are bound: ``step(*rest)``
    calls ``jitted(*bound, *rest)``, and ``lower`` and ``_cache_size``
    are the jitted function's on the same arguments."""

    def __init__(self, jitted, *bound):
        self.jitted, self.bound = jitted, bound

    def __call__(self, *rest):
        return self.jitted(*self.bound, *rest)

    def lower(self, *rest):
        return self.jitted.lower(*self.bound, *rest)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


def make_server_fleet_step(final_dnn, mesh: Mesh = None):
    """Batch the server-side DNN across streams.

    Returns ``server(decoded (N, T, H, W, C)) -> pytree of (N, T, ...)``
    dense outputs — ONE jitted apply (``final_dnn.apply``, the DNN's own
    forward pass at its stated precision) over the flattened (N*T) frame
    batch instead of the N per-stream ``final_dnn.predict`` Python calls
    the fleet engine used to make. The engine double-buffers this
    against the next chunk's camera step (dispatching it asynchronously
    before the host-side accuracy decode of the previous chunk), so
    server inference overlaps camera encode.

    The parameters are arguments of the jitted program, placed on the
    device once here (replicated over the stream mesh where there is
    one), not constants compiled into it: new weights reuse the program,
    and a large model's weights are not folded into its executable.

    ``mesh``: optional ``"stream"`` mesh; shards the stream axis with
    shard_map like the camera step (the backbone is per-frame, so the
    fleet axis stays embarrassingly parallel).
    """
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.vision.dnn import detection_keep_heat

    if mesh is not None:
        assert_addressable_mesh(mesh, "make_server_fleet_step")

    task = final_dnn.task

    def _server(params, decoded):
        N, T = decoded.shape[:2]
        flat = decoded.reshape((N * T,) + decoded.shape[2:])
        out = final_dnn.apply(params, flat)
        if task == "detection":
            # fold the NMS device half of detection decoding into the
            # batched program: the host-side decode is then numpy-only and
            # genuinely overlaps the next chunk's camera step
            out = dict(out, keep=detection_keep_heat(out))
        return jax.tree_util.tree_map(
            lambda v: v.reshape((N, T) + v.shape[1:]), out)

    if mesh is None:
        params = jax.device_put(final_dnn.params)
        return BoundStep(jax.jit(_server), params)
    spec = P(STREAM_AXIS)
    params = jax.device_put(final_dnn.params, NamedSharding(mesh, P()))
    sharded = shard_map(_server, mesh, in_specs=(P(), spec),
                        out_specs=spec)
    return BoundStep(jax.jit(sharded), params)


def make_accuracy_reduce_step(final_dnn, mesh: Mesh = None):
    """Device-side per-lane accuracy reduction for windowed aggregation.

    Returns ``acc(outs, ref_outs) -> (N,)`` where both arguments are the
    (N, T, ...) output trees of :func:`make_server_fleet_step`. With this
    step in the pipeline only O(N) accuracy scalars (plus the (N, T) byte
    matrix) ever cross to host per chunk — the full dense output trees
    stay on device, which is what makes ``detail="windowed"`` serving
    O(window) on the host instead of O(streams x chunks).

    Only built for tasks :func:`repro.vision.dnn.device_lane_accuracy`
    supports (segmentation, keypoint); the engine falls back to the
    batched host scorer for detection. Sharded over the stream mesh like
    the server step when ``mesh`` is given (the reduction is per-lane, so
    the fleet axis stays embarrassingly parallel).
    """
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.vision.dnn import device_lane_accuracy

    if mesh is not None:
        assert_addressable_mesh(mesh, "make_accuracy_reduce_step")

    task = final_dnn.task

    def _acc(outs, ref_outs):
        return device_lane_accuracy(task, outs, ref_outs)

    if mesh is None:
        return jax.jit(_acc)
    spec = P(STREAM_AXIS)
    sharded = shard_map(_acc, mesh, in_specs=(spec, spec), out_specs=spec)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# tenant-routed fleet steps (multi-tenant serving: one fleet, many DNNs)
# ---------------------------------------------------------------------------
def make_tenant_camera_fleet_step(tenants, impl: str = "fast",
                                  mesh: Mesh = None, mask: bool = False):
    """Tenant-routed camera step: ``step(chunks, tenant_ids[, active])``.

    Same contract as :func:`make_camera_fleet_step` plus a traced
    ``(N,)`` int32 tenant-id lane: scoring gathers each lane's AccModel
    parameters out of a stacked ``(T, ...)`` params tree (the
    ``models.moe`` routed-dispatch idiom — tenant mix is *data*, so
    re-mixing tenants at a fixed padded shape costs zero recompiles),
    and QP assignment applies each lane's own tenant
    :class:`~repro.core.quality.QualityConfig` by computing every
    tenant's (cheap, macroblock-resolution) QP map and selecting per
    lane — bit-identical per lane to a dedicated engine running that
    tenant's static config. ``qcfg.gamma`` must agree across tenants
    (static dilation window); the fused encoder fast-paths additionally
    need one shared config (``serve.tenants.validate_tenants`` enforces
    both loudly).
    """
    from repro.codec.codec import CHUNK_ENCODERS
    from repro.core.accmodel import accmodel_apply
    from repro.core.quality import dilate_scores, qp_maps_from_scores_batched
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.serve.tenants import gather_tree, stack_trees, validate_tenants

    tenants = validate_tenants(tenants, impl)
    if mesh is not None:
        assert_addressable_mesh(mesh, "make_tenant_camera_fleet_step")

    acc_stack = stack_trees([t.accmodel.params for t in tenants])
    qcfgs = [t.qcfg for t in tenants]
    enc = CHUNK_ENCODERS.resolve(impl)
    fused_scores = impl in ("fused", "fused_exact")
    if fused_scores:
        from repro.kernels.mbcodec.ops import encode_chunk_fused_scores
        enc_scores = functools.partial(encode_chunk_fused_scores,
                                       clip_refs=(impl == "fused_exact"))

    def _score(chunks, tids):
        heads = chunks[:, 0]
        return jax.nn.sigmoid(jax.vmap(
            lambda f, i: accmodel_apply(gather_tree(acc_stack, i),
                                        f[None])[0])(heads, tids))

    def _tenant_step(chunks, tids, active=None):
        scores = _score(chunks, tids)
        if fused_scores:
            # validate_tenants pinned one shared config for fused impls
            q = qcfgs[0]
            pooled = dilate_scores(scores, q.gamma)
            ktriple = jnp.array([q.alpha, float(q.qp_hi), float(q.qp_lo)],
                                jnp.float32)
            decoded, pbytes = jax.vmap(
                lambda c, p: enc_scores(c, p, ktriple))(chunks, pooled)
        else:
            # every tenant's two-level QP map on all lanes (macroblock
            # resolution: cheap next to the encode), then one per-lane
            # gather — each lane sees exactly its tenant's static map
            per_t = jnp.stack([qp_maps_from_scores_batched(scores, q)[0]
                               for q in qcfgs])
            qmaps = per_t[tids, jnp.arange(chunks.shape[0])]
            decoded, pbytes = jax.vmap(enc)(chunks, qmaps)
        if active is not None:  # zero padded lanes' wire bytes in-program
            lane = active.astype(pbytes.dtype)
            pbytes = pbytes * lane.reshape((-1,) + (1,) * (pbytes.ndim - 1))
        return decoded, pbytes, scores

    def _step(chunks, tids):
        return _tenant_step(chunks, tids)

    def _step_mask(chunks, tids, active):
        return _tenant_step(chunks, tids, active)

    fn = _step_mask if mask else _step
    if mesh is None:
        return jax.jit(fn)
    spec = P(STREAM_AXIS)
    in_specs = (spec, spec) + ((spec,) if mask else ())
    sharded = shard_map(fn, mesh, in_specs=in_specs,
                        out_specs=(spec, spec, spec))
    return jax.jit(sharded)


def make_tenant_server_fleet_step(tenants, mesh: Mesh = None):
    """Tenant-grouped server step: ``server(decoded, tenant_ids)`` ->
    union pytree of ``(N, T, ...)`` dense outputs.

    The backbone — which dominates server FLOPs — runs exactly once per
    lane with that lane's tenant parameters (per-lane gather out of the
    stacked backbone tree, so N lanes cost N backbone applies no matter
    how many tenants share the fleet — the capacity win the multitenant
    bench measures against dedicated fleets). Heads are grouped per
    task: each distinct task's heads run densely over all lanes with
    per-lane-gathered head parameters, and the output tree is the
    *union* of every task's keys — lanes of other tenants carry
    well-shaped garbage under foreign keys, which the host scorer (and
    the device accuracy reduce) never reads because it groups lanes by
    tenant. Padded admission lanes route to tenant 0 and are masked
    downstream exactly like today.

    Tenants must share backbone geometry (``stack_trees`` raises
    otherwise); heads within one task likewise.
    """
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.serve.tenants import gather_tree, stack_trees, validate_tenants
    from repro.vision.dnn import backbone, detection_keep_heat, head

    tenants = validate_tenants(tenants)
    if mesh is not None:
        assert_addressable_mesh(mesh, "make_tenant_server_fleet_step")

    bb_stack = stack_trees([t.dnn.params["backbone"] for t in tenants])
    # per task: its head-key -> stacked head params over the task's
    # members, plus the dense tenant-id -> position-in-task-stack map
    # (foreign tenants map to slot 0: their lanes compute valid-shaped
    # garbage that is masked at scoring)
    tasks = []
    seen = []
    for t in tenants:
        if t.task not in seen:
            seen.append(t.task)
            tasks.append(t.task)
    head_keys = {"detection": ("heat", "wh", "off"),
                 "segmentation": ("seg",), "keypoint": ("kp",)}
    task_specs = []
    for task in tasks:
        members = [i for i, t in enumerate(tenants) if t.task == task]
        stacks = {k: stack_trees([tenants[i].dnn.params[k]
                                  for i in members])
                  for k in head_keys[task]}
        pos = jnp.zeros(len(tenants), jnp.int32)
        for slot, i in enumerate(members):
            pos = pos.at[i].set(slot)
        task_specs.append((task, stacks, pos))

    def _server(decoded, tids):
        N, T = decoded.shape[:2]
        # one lax.map over lanes, params gathered per lane: inside the
        # loop every conv runs with ordinary (unbatched) kernels, which
        # lowers to the fast conv path — a vmap over lane-varying
        # kernels hits XLA's batched-kernel lowering and costs ~1.3x,
        # enough to erase the shared fleet's lane advantage outright
        bb_lane = gather_tree(bb_stack, tids)
        hstacks_lane = []
        for task, stacks, pos in task_specs:
            hidx = pos[tids]
            hstacks_lane.append({k: gather_tree(h, hidx)
                                 for k, h in stacks.items()})

        def one_lane(args):
            frames, bb_p, heads_p = args
            feats = backbone(bb_p, frames)
            return {k: head(p, feats)
                    for hp in heads_p for k, p in hp.items()}

        out = jax.lax.map(one_lane, (decoded, bb_lane, hstacks_lane))
        if "heat" in out:
            flat = {"heat": out["heat"].reshape(
                (N * T,) + out["heat"].shape[2:])}
            out["keep"] = detection_keep_heat(flat).reshape(
                (N, T) + out["heat"].shape[2:-1])
        return out

    if mesh is None:
        return jax.jit(_server)
    spec = P(STREAM_AXIS)
    sharded = shard_map(_server, mesh, in_specs=(spec, spec),
                        out_specs=spec)
    return jax.jit(sharded)


def make_tenant_accuracy_reduce_step(tenants, mesh: Mesh = None):
    """Tenant-routed device accuracy reduce: ``acc(outs, ref_outs,
    tenant_ids) -> (N,)`` over the tenant server step's union trees.
    Each distinct task's :func:`~repro.vision.dnn.device_lane_accuracy`
    runs over all lanes and the per-lane result selects by tenant task —
    only built when every tenant's task reduces on device (the engine
    falls back to grouped host scoring otherwise)."""
    from repro.distributed.mesh import STREAM_AXIS
    from repro.distributed.sharding import assert_addressable_mesh
    from repro.serve.tenants import validate_tenants
    from repro.vision.dnn import device_lane_accuracy

    tenants = validate_tenants(tenants)
    if mesh is not None:
        assert_addressable_mesh(mesh, "make_tenant_accuracy_reduce_step")

    tasks = []
    for t in tenants:
        if t.task not in tasks:
            tasks.append(t.task)
    task_idx = jnp.array([tasks.index(t.task) for t in tenants],
                         jnp.int32)

    def _acc(outs, ref_outs, tids):
        vals = [device_lane_accuracy(task, outs, ref_outs)
                for task in tasks]
        if len(vals) == 1:
            return vals[0]
        sel = task_idx[tids]
        acc = vals[0]
        for k in range(1, len(vals)):
            acc = jnp.where(sel == k, vals[k], acc)
        return acc

    if mesh is None:
        return jax.jit(_acc)
    spec = P(STREAM_AXIS)
    sharded = shard_map(_acc, mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)
    return jax.jit(sharded)


def make_prefill_step(model, cfg: ArchConfig, rules: Rules):
    def prefill(params, batch):
        extras = {k: batch[k] for k in ("context", "frames") if k in batch}
        cache, last_logits = model.prefill(params, batch["tokens"], extras)
        return cache, last_logits

    return prefill


def make_decode_step(model, cfg: ArchConfig, rules: Rules):
    def decode(params, cache, token, pos, extra_ctx=None):
        extras = {"context": extra_ctx} if extra_ctx is not None else {}
        new_cache, logits = model.decode(params, cache, token, pos, extras)
        next_token = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return new_cache, next_token, logits

    return decode


def greedy_generate(model, params, prompt, steps: int, cache=None):
    """Reference autoregressive loop (examples / equivalence tests)."""
    B, S = prompt.shape
    if cache is None:
        cache = model.init_cache(B, S + steps)
    # prefill by stepping token-by-token (exactness oracle for tests)
    tok = prompt[:, :1]
    outs = []
    for t in range(S + steps - 1):
        cache, logits = model.decode(params, cache, tok, t)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(prompt.dtype)
        if t + 1 < S:
            tok = prompt[:, t + 1 : t + 2]
        else:
            tok = nxt
            outs.append(nxt)
    return jnp.concatenate(outs, axis=1) if outs else prompt[:, :0]
