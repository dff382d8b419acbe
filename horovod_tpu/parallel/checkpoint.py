"""Sharding-aware checkpoint/resume for the hybrid-mesh transformer.

The replicated-DP path checkpoints through ``trainer.save_checkpoint``
(rank-0 numpy write + broadcast-on-restore — the reference's §5.4
protocol, ``keras_imagenet_resnet50.py:47-56``). The hybrid-mesh
(dp x sp x tp x ep / pp) training state is different: params and optimizer
state are GLOBAL jax.Arrays laid out by ``NamedSharding`` over the mesh —
gathering them to one host numpy tree would defeat the point of sharding
(and OOM at scale). Here orbax writes each array with its sharding
(every process writes its addressable shards) and restores arrays BACK
onto the target mesh layout taken from a template tree, so a run can
restart on the same mesh shape and bit-continue.

Resume protocol parity: ``latest_step`` is the rank-0 scan of the
reference, and in a multi-process world ``restore_sharded`` broadcasts
the resolved step from rank 0 (object broadcast over the coordination
plane) so every process resumes the same epoch even if the filesystem
view races.
"""

from __future__ import annotations

import json
import os
import sys
import zlib
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import runtime
from ..exceptions import CheckpointCorruptError
from ..trainer import apply_retention, latest_checkpoint_step


def _ckpt_path(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), f"ckpt_{step}")


# ---------------------------------------------------------------------------
# Integrity manifests (Check-N-Run-style, Eisenman et al. NSDI '22): every
# save writes a per-leaf checksum manifest alongside the checkpoint bytes, so
# a restore can PROVE the bytes it is about to trust are the bytes that were
# written — torn writes, truncation and bit rot are routine at fleet scale,
# and orbax's tensorstore layout does not end-to-end-checksum array data (a
# flipped byte in a ``d/`` chunk restores "successfully" as garbage).
# ---------------------------------------------------------------------------

MANIFEST_NAME = "hvd_manifest.json"


def _leaf_crc(leaf: Any) -> Optional[int]:
    """CRC32 of a leaf's canonical serialized bytes, or None when the leaf
    is not host-readable (a non-fully-addressable jax.Array in a
    multi-process world — its record still pins structure/dtype/shape)."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        return None
    arr = np.asarray(leaf)
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


def _leaf_records(tree: Any) -> List[dict]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    records = []
    for path, leaf in flat:
        arr_like = (leaf if isinstance(leaf, jax.Array)
                    else np.asarray(leaf))
        records.append({
            "path": jax.tree_util.keystr(path),
            "shape": list(np.shape(arr_like)),
            "dtype": str(np.asarray(leaf).dtype
                         if not isinstance(leaf, jax.Array)
                         else leaf.dtype),
            "crc32": _leaf_crc(leaf),
        })
    return records


def write_manifest(path: str, tree: Any, step: Optional[int] = None,
                   extra_meta: Optional[dict] = None) -> str:
    """Write the integrity manifest for the checkpoint at ``path``.

    Called by both checkpoint flavors (``trainer.save_checkpoint`` and
    :func:`save_sharded`) strictly AFTER the orbax write finalizes and
    strictly BEFORE the elastic two-phase commit marker — a marker-bearing
    step therefore always has a manifest, and a crash at any point leaves
    either no manifest (step not committed, invisible to restore) or a
    complete one. The manifest lives INSIDE the checkpoint directory so
    retention GC removes it with the bytes it describes.

    Records the tree's per-leaf CRC32/shape/dtype plus the world and mesh
    shape that wrote it (diagnostic metadata: elastic restarts may
    legitimately restore onto a different world, so verification checks
    leaves, not worlds).
    """
    meta: dict = {"format": 1, "leaves": _leaf_records(tree)}
    if extra_meta:
        meta.update(extra_meta)
    if step is not None:
        meta["step"] = int(step)
    if runtime.is_initialized():
        meta["world_size"] = runtime.size()
        try:
            meta["mesh_shape"] = dict(runtime.mesh().shape)
        except Exception:  # noqa: BLE001 — metadata only, never fatal
            meta["mesh_shape"] = None
    manifest_path = os.path.join(path, MANIFEST_NAME)
    tmp = manifest_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, manifest_path)
    return manifest_path


def read_manifest(path: str) -> Optional[dict]:
    """Load the manifest for the checkpoint at ``path``; None when the
    checkpoint predates integrity manifests (legacy, unverifiable)."""
    manifest_path = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        return None
    try:
        with open(manifest_path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            path, f"unreadable manifest {MANIFEST_NAME}: {e!r}") from e


def _verify_leaves(path: str, manifest: dict, restored_tree: Any,
                   subset: bool = False) -> int:
    """Match restored leaves against manifest records; raises
    :class:`CheckpointCorruptError` naming the first offending leaf.

    Matching is a multiset over (shape, dtype, crc), not a path-by-path
    walk: orbax restores container types structurally (dataclasses and
    NamedTuples come back as dicts/lists), so save-time and restore-time
    keypaths need not be comparable — but the bytes must be. ``subset``
    allows the restored tree to cover only part of the manifest (the
    partial ``restore_for_inference`` read). Returns the number of leaves
    whose CRC was actually checked.
    """
    expected: dict = {}
    for rec in manifest.get("leaves", []):
        key = (tuple(rec["shape"]), str(rec["dtype"]))
        expected.setdefault(key, []).append(rec)
    flat, _ = jax.tree_util.tree_flatten_with_path(restored_tree)
    if not subset:
        n_expected = sum(len(v) for v in expected.values())
        if len(flat) != n_expected:
            raise CheckpointCorruptError(
                path, f"manifest records {n_expected} leaves but the "
                      f"checkpoint restored {len(flat)}")
    checked = 0
    for keypath, leaf in flat:
        name = jax.tree_util.keystr(keypath)
        arr = np.asarray(leaf)
        key = (tuple(arr.shape), str(arr.dtype))
        candidates = expected.get(key)
        if not candidates:
            # A scalar's container type may not round-trip (0-d float32
            # saved as a python scalar restores as float64) — retry under
            # each manifest dtype with a value-preserving cast.
            recast = [(k, rs) for k, rs in expected.items()
                      if k[0] == tuple(arr.shape) and rs]
            for k, rs in recast:
                try:
                    cast = np.asarray(leaf, dtype=np.dtype(k[1]))
                except (TypeError, ValueError):
                    continue
                crc = zlib.crc32(np.ascontiguousarray(cast).tobytes())
                hit = next((r for r in rs if r["crc32"] == crc), None)
                if hit is not None:
                    rs.remove(hit)
                    checked += 1
                    break
            else:
                raise CheckpointCorruptError(
                    path, f"leaf {name} with shape {arr.shape} dtype "
                          f"{arr.dtype} matches no manifest record")
            continue
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
        hit = next((r for r in candidates if r["crc32"] == crc), None)
        if hit is None:
            # Unverifiable records (crc None — non-addressable at save
            # time) match any leaf of their shape/dtype.
            hit = next((r for r in candidates if r["crc32"] is None), None)
            if hit is None:
                want = ", ".join(r["path"] for r in candidates[:3])
                raise CheckpointCorruptError(
                    path, f"leaf {name} (shape {arr.shape}, dtype "
                          f"{arr.dtype}) CRC mismatch — bytes differ from "
                          f"what the manifest recorded for {want}")
            candidates.remove(hit)
            continue
        candidates.remove(hit)
        checked += 1
    return checked


def verify_checkpoint(path: str, *, allow_unverified: bool = True) -> bool:
    """Verify the checkpoint at ``path`` against its integrity manifest.

    Reads the full checkpoint into host memory (raw numpy, no template)
    and checks every leaf's CRC32/shape/dtype plus the leaf count against
    the manifest. Raises :class:`CheckpointCorruptError` naming the path
    and the offending leaf on any mismatch — including an orbax read that
    fails outright (truncated metadata, missing chunk files).

    Returns True when verification ran, False for a manifest-less legacy
    checkpoint (tolerated when ``allow_unverified``, raised otherwise).
    This is a full-read operation: the restore chain calls it once per
    restore attempt, not per step.
    """
    import orbax.checkpoint as ocp
    if not os.path.isdir(path):
        raise CheckpointCorruptError(path, "checkpoint directory missing")
    manifest = read_manifest(path)
    if manifest is None:
        if allow_unverified:
            return False
        raise CheckpointCorruptError(
            path, f"no {MANIFEST_NAME} — cannot verify integrity")
    try:
        restored = ocp.PyTreeCheckpointer().restore(path)
    except CheckpointCorruptError:
        raise
    except Exception as e:  # noqa: BLE001 — any read failure IS corruption
        raise CheckpointCorruptError(
            path, f"unreadable checkpoint: {type(e).__name__}: {e}") from e
    _verify_leaves(path, manifest, restored)
    return True


# ---------------------------------------------------------------------------
# ZeRO (rank-sharded) optimizer state: checkpoints store the WORLD-AGNOSTIC
# canonical form — each stacked [nshards, shard_len] shard array becomes the
# flat unpadded vector it encodes, identical no matter how many ranks wrote
# it — so an elastic restart may restore at a different world size and the
# restore re-shards onto the new world's layout (docs/checkpointing.md).
# ---------------------------------------------------------------------------


def _is_zero_state(x) -> bool:
    from ..optimizer import ZeroShardedState
    return isinstance(x, ZeroShardedState)


def _has_zero_state(tree: Any) -> bool:
    return any(_is_zero_state(l) for l in jax.tree_util.tree_leaves(
        tree, is_leaf=_is_zero_state))


def _zero_mesh_meta(tree: Any) -> Optional[dict]:
    """Mesh layout of the tree's first ZeRO plan (diagnostic metadata for
    the manifest): shard count plus, on a hybrid mesh, the scatter axis
    and the nonscatter axis sizes — so a mesh-reshape restore can log
    exactly what it is re-sharding across. None for ZeRO-free trees."""
    for l in jax.tree_util.tree_leaves(tree, is_leaf=_is_zero_state):
        if _is_zero_state(l):
            meta = {"nshards": int(l.plan.nshards)}
            if l.plan.hybrid:
                meta["scatter_axis"] = l.plan.scatter_axis
                meta["nonscatter"] = {a: int(n)
                                      for a, n in l.plan.nonscatter}
            return meta
    return None


def _zero_stays_sharded(x) -> bool:
    """A ZeRO node whose stacked arrays are not fully addressable (a
    jax.distributed world where other processes own part of them) cannot
    be canonicalized on this host — it is written AND restored in the
    sharded layout (orbax handles both collectively), and such
    checkpoints restore at the same world size only. Save and restore
    must take the same branch, so both consult this predicate."""
    return any(isinstance(l, jax.Array) and not l.is_fully_addressable
               for l in jax.tree_util.tree_leaves(x.inner))


def _canonicalize_zero(tree: Any, placeholders: bool = False) -> Any:
    """Replace every :class:`~horovod_tpu.optimizer.ZeroShardedState` node
    with its canonical (flat, unpadded, world-agnostic) form. Nodes kept
    sharded by :func:`_zero_stays_sharded` pass through unchanged — also
    when building restore templates (``placeholders=True``), since the
    checkpoint's bytes are then in the sharded layout too. No-op for
    trees without ZeRO state."""
    from ..optimizer import zero_to_canonical

    def _one(x):
        if not _is_zero_state(x) or _zero_stays_sharded(x):
            return x
        return zero_to_canonical(x, placeholders=placeholders)

    return jax.tree_util.tree_map(_one, tree, is_leaf=_is_zero_state)


def _restore_zero(template_tree: Any, restored_tree: Any) -> Any:
    """Re-shard canonically-restored ZeRO nodes onto ``template_tree``'s
    world layout (stacking + padding + the template leaves' shardings);
    nodes restored in the sharded layout (:func:`_zero_stays_sharded`)
    and all other restored leaves pass through untouched."""
    from ..optimizer import zero_from_canonical

    def _one(t, r):
        if _is_zero_state(t) and not _zero_stays_sharded(t):
            return zero_from_canonical(r.inner, t)
        return r

    return jax.tree_util.tree_map(_one, template_tree, restored_tree,
                                  is_leaf=_is_zero_state)


def snapshot_to_host(tree: Any, timeline: Any = None) -> Any:
    """The snapshot half of an async checkpoint (``CKPT_SNAPSHOT`` timeline
    phase): one bulk device→host fetch of a pytree into numpy.

    This is the ONLY part of a save that needs the live device state — the
    returned host copy is immutable, so the training loop may donate or
    overwrite the device buffers while a background writer (e.g.
    :class:`horovod_tpu.trainer.AsyncCheckpointer`) serializes. A single
    ``jax.device_get`` over the whole tree batches the D2H transfers
    instead of syncing leaf-by-leaf.
    """
    from ..utils import timeline as _tl
    with _tl.maybe_op(timeline, "ckpt.snapshot", _tl.CKPT_SNAPSHOT):
        return jax.device_get(tree)


def save_sharded(directory: str, step: int, params: Any,
                 opt_state: Any, max_to_keep: Optional[int] = None) -> str:
    """Write the sharded (params, opt_state) trees at ``step``.

    Every process participates (orbax writes each process's addressable
    shards); retention mirrors ``trainer.save_checkpoint`` and runs on
    rank 0 only. After the orbax write finalizes, rank 0 writes the
    per-leaf integrity manifest (:func:`write_manifest`) into the
    checkpoint directory — strictly before any elastic commit marker, so
    a marker-bearing step is always verifiable.

    ZeRO optimizer state is written in its canonical world-agnostic form
    (:func:`_canonicalize_zero`: flat unpadded bucket vectors; on hybrid
    meshes the 2-D form — flat GLOBAL bucket vectors, identical across
    (dp, tp) reshapes), so the manifest CRCs — and therefore
    :func:`verify_checkpoint` and the elastic fallback walk — hold across
    world-size changes AND mesh reshapes, and :func:`restore_sharded` can
    re-shard onto a different world or mesh. The manifest records the
    writing plan's mesh layout (``zero_mesh``) so the restore can log the
    reshape it performs.
    """
    import orbax.checkpoint as ocp
    path = _ckpt_path(directory, step)
    live = {"params": params, "opt_state": opt_state}
    zero_mesh = _zero_mesh_meta(live)
    tree = _canonicalize_zero(live)
    if all(not isinstance(l, jax.Array) or l.is_fully_addressable
           for l in jax.tree_util.tree_leaves(tree)):
        # One bulk device→host fetch feeds BOTH the orbax write and the
        # manifest CRCs; letting the manifest's per-leaf np.asarray run
        # against the device tree would transfer the whole state a
        # second time per commit. Restore placement is unaffected —
        # restore_sharded lays leaves out from the TEMPLATE's
        # ArrayRestoreArgs, not the saved arrays' sharding. Skipped in
        # multi-process worlds: the orbax save must see the global
        # jax.Arrays there (each process contributes its shards), and
        # non-addressable leaves never pay a host fetch anyway (their
        # manifest CRC is None).
        tree = snapshot_to_host(tree)
    ckptr = ocp.PyTreeCheckpointer()
    ckptr.save(path, tree, force=True)
    if (not runtime.is_initialized()
            or runtime.world().controller_rank == 0
            or runtime.world().env_world):
        # Rank 0 owns the shared directory in a jax.distributed world;
        # env-world ranks each own a PRIVATE directory and must manifest
        # their own copy (elastic restore verifies per-rank).
        write_manifest(path, tree, step=step,
                       extra_meta={"zero_mesh": zero_mesh}
                       if zero_mesh else None)
    if (not runtime.is_initialized()
            or runtime.world().controller_rank == 0):
        apply_retention(directory, path, max_to_keep)
    return path


def restore_sharded(directory: str, params_template: Any,
                    opt_state_template: Any,
                    step: Optional[int] = None,
                    verify: bool = True
                    ) -> Tuple[Any, Any, int]:
    """Restore (params, opt_state) onto the template trees' shardings.

    ``*_template`` supply structure, dtypes and target ``NamedSharding``s
    — the trees ``init_state`` returns work directly (their values are
    discarded). Returns ``(params, opt_state, step)``; in a multi-process
    world the resolved step comes from rank 0's directory scan, so all
    ranks agree even when the shared filesystem is eventually consistent.

    ``verify`` (default on) checks the integrity manifest first and
    raises :class:`~horovod_tpu.exceptions.CheckpointCorruptError` on a
    mismatch instead of silently resuming from garbage; pass False when
    the caller already verified this step (the elastic fallback walk).

    ZeRO optimizer state restores through its canonical world-agnostic
    form and is RE-SHARDED onto the template's world: a checkpoint
    committed by an 8-rank run restores into a 4-rank (or 16-rank)
    world's :class:`~horovod_tpu.optimizer.ZeroShardedState` templates,
    provided the model and ``HOROVOD_FUSION_THRESHOLD`` (the bucket
    plan) are unchanged.
    """
    import orbax.checkpoint as ocp
    if step is None:
        step = latest_checkpoint_step(directory)
    if runtime.is_initialized() and runtime.size() > 1:
        from ..ops.collectives import broadcast_object
        step = broadcast_object(step, root_rank=0)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    path = _ckpt_path(directory, int(step))
    if verify:
        verify_checkpoint(path)
    template = {"params": params_template, "opt_state": opt_state_template}
    # ZeRO nodes restore via np placeholders in the canonical layout (the
    # checkpoint's format); everything else keeps the template leaf and
    # its sharding.
    canon_template = _canonicalize_zero(template, placeholders=True)
    if _has_zero_state(template):
        manifest = read_manifest(path)
        saved_world = manifest.get("world_size") if manifest else None
        if (runtime.is_initialized() and saved_world is not None
                and saved_world != runtime.size()):
            print(f"[ckpt] re-sharding ZeRO optimizer state: checkpoint "
                  f"written by a world of {saved_world}, restoring into "
                  f"{runtime.size()}", file=sys.stderr, flush=True)
        saved_zm = manifest.get("zero_mesh") if manifest else None
        cur_zm = _zero_mesh_meta(template)
        if saved_zm is not None and cur_zm is not None \
                and saved_zm != cur_zm:
            # 2-D canonical form at work: same global bytes, new (dp, tp)
            # split — e.g. a (dp=4, tp=2) checkpoint restoring at
            # (dp=2, tp=4).
            print(f"[ckpt] re-sharding ZeRO optimizer state across mesh "
                  f"reshape: {saved_zm} -> {cur_zm}",
                  file=sys.stderr, flush=True)

    def _restore_args(x):
        if isinstance(x, jax.Array) or isinstance(x, jax.ShapeDtypeStruct):
            return ocp.ArrayRestoreArgs(sharding=x.sharding,
                                        global_shape=x.shape,
                                        dtype=x.dtype)
        return ocp.RestoreArgs()

    ckptr = ocp.PyTreeCheckpointer()
    restored = ckptr.restore(
        path, item=canon_template,
        restore_args=jax.tree_util.tree_map(_restore_args, canon_template))
    restored = _restore_zero(template, restored)
    return restored["params"], restored["opt_state"], int(step)


# ---------------------------------------------------------------------------
# LoRA adapter persistence (the multi-tenant serving plane): one directory
# per adapter, manifest-CRC-verified exactly like the base checkpoints, so
# a hot-load can PROVE the delta it is about to serve. An adapter is tiny
# (rank-r pairs; parallel/lora.py has the math) — the full-read verify that
# would be expensive per training commit costs microseconds here.
# ---------------------------------------------------------------------------


def adapter_path(directory: str, name: str) -> str:
    """Where the adapter ``name`` lives under ``directory``
    (``adapter_<name>``, next to the base ``ckpt_<step>`` dirs). The
    name rule is shared with :class:`~horovod_tpu.serve.adapters.
    AdapterRegistry` (one identifier grammar everywhere an adapter name
    travels — paths, labels, prefix-reuse salts)."""
    from .lora import check_adapter_name
    check_adapter_name(name)
    return os.path.join(os.path.abspath(directory), f"adapter_{name}")


def save_adapter(directory: str, name: str, adapter: Any) -> str:
    """Write the adapter tree to ``<directory>/adapter_<name>`` with its
    integrity manifest (:func:`write_manifest` — same ordering contract
    as the base flavors: manifest strictly after the orbax write
    finalizes). Base checkpoints in the same directory are untouched;
    returns the adapter path."""
    import orbax.checkpoint as ocp
    path = adapter_path(directory, name)
    tree = jax.tree_util.tree_map(np.asarray, adapter)
    ocp.PyTreeCheckpointer().save(path, tree, force=True)
    write_manifest(path, tree, extra_meta={"adapter_name": name})
    return path


def restore_adapter(directory: str, name: str, *,
                    verify: bool = True) -> Any:
    """Read the adapter ``name`` back as a host tree, CRC-verifying every
    leaf against its manifest first (the same verify walk the base
    restore chain uses): a corrupt adapter raises
    :class:`~horovod_tpu.exceptions.CheckpointCorruptError` naming the
    path and the offending leaf — and the base weights it would have
    ridden on are never touched, so one tenant's rotted delta cannot
    take the whole engine down."""
    import orbax.checkpoint as ocp
    path = adapter_path(directory, name)
    if not os.path.isdir(path):
        raise FileNotFoundError(f"no adapter {name!r} under {directory} "
                                f"(looked for {path})")
    try:
        restored = ocp.PyTreeCheckpointer().restore(path)
    except Exception as e:  # noqa: BLE001 — any read failure IS corruption
        raise CheckpointCorruptError(
            path, f"unreadable adapter: {type(e).__name__}: {e}") from e
    if verify:
        manifest = read_manifest(path)
        if manifest is None:
            raise CheckpointCorruptError(
                path, f"no {MANIFEST_NAME} — cannot verify adapter "
                      f"integrity")
        _verify_leaves(path, manifest, restored)
    return restored


#: restore_for_inference's serving dtypes. None = as stored; "int8" is
#: weight-only per-channel quantization (ops/quant.py) the generation
#: forward dequantizes in-jit.
INFERENCE_DTYPES = (None, "fp32", "bf16", "int8")


def _inference_cast(variables: Any, dtype: Optional[str]) -> Any:
    """Apply the serving dtype AFTER restore+CRC-verify: manifests record
    the stored fp32 bytes, so verification must never see the quantized
    or downcast view (the int8 round-trip contract)."""
    if dtype is None:
        return variables
    if dtype == "int8":
        from ..ops.quant import quantize_tree
        return quantize_tree(variables)
    target = {"fp32": np.float32, "bf16": jnp.bfloat16}[dtype]

    def _one(x):
        a = np.asarray(x)
        return a.astype(target) if np.issubdtype(a.dtype, np.floating) \
            else a

    return jax.tree_util.tree_map(_one, variables)


def restore_for_inference(directory: str, step: Optional[int] = None, *,
                          mesh=None, spec_fn=None,
                          dtype: Optional[str] = None) -> Any:
    """Load a checkpoint's serving state — the restore entry point behind
    :mod:`horovod_tpu.serve`.

    Reads the newest (or ``step``-selected) ``ckpt_<step>`` under
    ``directory`` and returns the model *variables* dict the inference
    ``apply`` consumes: ``{"params": ...}`` plus ``"batch_stats"`` when
    the checkpoint carries BN statistics. Works on both checkpoint
    flavors this framework writes — the replicated ``save_checkpoint``
    TrainState pytree (``{step, params, opt_state, batch_stats}``) and
    the hybrid-mesh ``save_sharded`` tree (``{params, opt_state}``) —
    because serving needs neither the optimizer state nor the step: the
    training-only subtrees are dropped unread rather than restored and
    discarded.

    ``dtype`` picks the serving precision (:data:`INFERENCE_DTYPES`;
    validated eagerly, before any checkpoint I/O): ``None`` serves the
    stored dtypes, ``"fp32"``/``"bf16"`` cast every float leaf, and
    ``"int8"`` quantizes matmul weights (float leaves of ndim >= 2) to
    :class:`~horovod_tpu.ops.quant.QuantizedTensor` — int8 payload +
    per-channel f32 scales that the generation forward dequantizes
    in-jit (weights stay int8 in HBM). Quantization happens strictly
    AFTER manifest verification: CRCs are checked against the stored
    fp32 leaves, never the quantized view, so ``verify_checkpoint`` and
    the int8 serving path see the same bytes.

    With ``mesh`` set, every leaf is placed as a global ``jax.Array``
    laid out by :func:`horovod_tpu.parallel.mesh.named_sharding_tree`
    (``spec_fn`` picks per-leaf ``PartitionSpec``s; default fully
    replicated) — so a model too big for one chip serves sharded across
    the slice with zero model-code changes. Without ``mesh``, plain host
    numpy comes back (single-host serving).

    A truncated or otherwise unreadable checkpoint raises
    :class:`~horovod_tpu.exceptions.CheckpointCorruptError` naming the
    path — never a raw orbax/tensorstore traceback — and when an
    integrity manifest is present the restored serving subtrees are
    CRC-verified against it (a subset check: the training-only subtrees
    stay unread, which is the point of the partial restore).
    """
    if dtype not in INFERENCE_DTYPES:
        raise ValueError(
            f"restore_for_inference dtype={dtype!r} is not supported; "
            f"supported: {INFERENCE_DTYPES} (None = as stored)")
    import orbax.checkpoint as ocp
    if step is None:
        step = latest_checkpoint_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = _ckpt_path(directory, int(step))
    ckptr = ocp.PyTreeCheckpointer()
    # Structure first (metadata reads no array bytes), then a PARTIAL
    # restore of just the serving subtrees: for an Adam-style optimizer
    # the opt_state is ~2x the params, so a full read would triple the
    # restore I/O and peak host memory of every server start.
    try:
        meta = ckptr.metadata(path)
    except Exception as e:  # noqa: BLE001 — surface as corruption, named
        raise CheckpointCorruptError(
            path, f"unreadable checkpoint metadata: "
                  f"{type(e).__name__}: {e}") from e
    # orbax's metadata() returns a StepMetadata whose item_metadata.tree
    # is the stored structure; it swallows a read failure into
    # item_metadata=None (after logging), which here is corruption.
    if meta.item_metadata is None:
        raise CheckpointCorruptError(
            path, "unreadable checkpoint metadata (orbax found no item "
                  "metadata it could read)")
    meta = meta.item_metadata.tree
    if "params" not in meta:
        raise ValueError(
            f"{path} has no 'params' subtree — not a checkpoint this "
            f"framework wrote (keys: {sorted(meta)})")
    item = {k: meta[k] for k in ("params", "batch_stats")
            if meta.get(k) is not None}
    try:
        variables = ckptr.restore(
            path, item=item, transforms={},
            restore_args=jax.tree_util.tree_map(lambda _: ocp.RestoreArgs(),
                                                item))
    except Exception as e:  # noqa: BLE001
        raise CheckpointCorruptError(
            path, f"unreadable checkpoint: {type(e).__name__}: {e}") from e
    manifest = read_manifest(path)
    if manifest is not None:
        _verify_leaves(path, manifest, variables, subset=True)
    variables = _inference_cast(variables, dtype)
    if mesh is None:
        return variables
    from .mesh import named_sharding_tree
    shardings = named_sharding_tree(mesh, variables, spec_fn)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(np.asarray(x), s),
        variables, shardings)
