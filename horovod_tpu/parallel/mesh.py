"""Multi-axis device meshes: dp / tp / pp / sp / ep.

Beyond reference parity (the reference is data-parallel only, SURVEY §2.4);
these are the TPU-era parallelism axes the framework exposes so long-context
and large-model training are first-class. A hybrid mesh lays ranks out so
that the fastest-varying (innermost) axes map to physically close chips —
tensor/sequence parallelism wants ICI-neighbor bandwidth, data parallelism
tolerates DCN.

Axis names (canonical across the framework):

- ``dp`` — data parallel (gradient psum; the reference's world axis)
- ``tp`` — tensor parallel (Megatron-style sharded matmuls)
- ``pp`` — pipeline parallel (stage-to-stage ppermute)
- ``sp`` — sequence/context parallel (ring attention / all-to-all)
- ``ep`` — expert parallel (MoE dispatch over all_to_all)
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "pp", "ep", "sp", "tp")


def create_hybrid_mesh(dp: int = 1, tp: int = 1, pp: int = 1, sp: int = 1,
                       ep: int = 1,
                       devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh over the axes with size > 1 (plus ``dp`` always).

    Axis order is outermost→innermost ``(dp, pp, ep, sp, tp)``: tp/sp vary
    fastest so they land on ICI-adjacent chips; dp is outermost so its
    collectives can ride DCN across hosts ("How to Scale Your Model" mesh
    recipe).

    Every axis feeds the same spec-grouped gradient-sync plan
    (``ops/fusion.plan_grad_sync``): a leaf psums over exactly the axes
    it is replicated across, so growing the mesh — 3-D dp×tp×pp for the
    pipelined family, ``ep`` for MoE experts — changes PartitionSpecs,
    never step-body collective code (parity-pinned in
    tests/test_parallel.py).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    sizes = {"dp": dp, "pp": pp, "ep": ep, "sp": sp, "tp": tp}
    total = math.prod(sizes.values())
    if total != len(devs):
        knobs = {"dp": "dp= (create_hybrid_mesh(dp=), examples --dp)",
                 "pp": "pp= (examples --pp)",
                 "ep": "ep= (set n_experts to the ep size)",
                 "sp": "sp= (examples --sp)",
                 "tp": "tp= (create_hybrid_mesh(tp=), examples --tp)"}
        detail = ", ".join(f"{a}={sizes[a]} via {knobs[a]}" for a in AXES
                           if sizes[a] != 1) or "all axes at their default 1"
        raise ValueError(
            f"mesh {sizes} needs {total} devices, have {len(devs)}: the "
            f"axis sizes ({detail}) must multiply to the visible device "
            f"count — adjust the knobs above, or the device count "
            f"(JAX_PLATFORMS / --xla_force_host_platform_device_count), "
            f"or pass an explicit devices= subset")
    names = tuple(a for a in AXES if sizes[a] > 1) or ("dp",)
    shape = tuple(sizes[a] for a in names)
    return Mesh(np.array(devs).reshape(shape), names)


def axis_size(mesh: Mesh, name: str) -> int:
    """Size of ``name`` on ``mesh``; 1 for a canonical axis the mesh does
    not carry. A name that is neither on the mesh nor in :data:`AXES`
    raises — a typo ('dpp') must not silently read as "absent, size 1"
    and quietly skip a collective."""
    if name in mesh.shape:
        return int(mesh.shape[name])
    if name not in AXES:
        raise ValueError(
            f"unknown mesh axis {name!r}: this mesh has "
            f"{tuple(mesh.axis_names)} and the canonical axis names are "
            f"{AXES} (absent canonical axes have size 1)")
    return 1


def named_sharding_tree(mesh: Mesh, tree, spec_fn=None):
    """A tree of ``NamedSharding`` matching ``tree``'s structure.

    ``spec_fn(path, leaf) -> PartitionSpec | None`` picks each leaf's
    layout (``path`` is the ``jax.tree_util`` key-path tuple); ``None``
    (and the default ``spec_fn=None``) means fully replicated. This is
    the placement half of the serve/restore path: training code gets its
    shardings from the step builder, but a restore-for-inference has no
    step to inherit from — the checkpoint tree plus a rule is the whole
    specification.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def shard(path, leaf):
        spec = spec_fn(path, leaf) if spec_fn is not None else None
        return NamedSharding(mesh, spec if spec is not None else P())

    return jax.tree_util.tree_map_with_path(shard, tree)


def grad_sync_by_spec(grads, specs, mesh_axes, *, skip_axes=(),
                      wire_dtype=None):
    """Gradient sync for spec-sharded parameter trees (runs INSIDE
    shard_map). The per-leaf EMPIRICAL REFERENCE of the sync rule: every
    production plane now interprets the fused spec-grouped plan
    (``ops/fusion.plan_grad_sync`` → ``GradSync``, one collective per
    reduce-axis group) instead of calling this walk, but this function
    remains the ground truth the plan's membership and denominators are
    parity-pinned against in tests — the collective-gradient math is
    subtle enough that an executable reference is how drift gets caught.

    Each leaf's gradient is averaged (``pmean``) over every mesh axis the
    leaf is REPLICATED across (all axes not in its own PartitionSpec and
    not in ``skip_axes`` — e.g. ``pp``, where each stage owns its own
    weights outright).

    tp-sharded leaves additionally divide by the tp axis size: under
    full-manual shard_map (check_vma=False) the transpose of the
    row-parallel ``psum`` is ``psum``, so the replicated cotangent
    entering each tp-local matmul arrives multiplied by tp — one spurious
    factor of tp on every tp-sharded weight's gradient (verified
    empirically: tp=2 vs tp=1 from identical params gave exactly 2x
    before this correction; replicated leaves are unaffected because
    their per-rank partials go through the pmean above, and the factor
    does not compound across layers because partial cotangents are
    re-summed — not amplified — by the next psum transpose).

    ``wire_dtype`` (``"bf16"``/``"fp8"``) runs each replicated-axis
    gradient average on the wire in reduced precision — same contract as
    the fused-bucket planes (``ops/fusion.py``): the ``1/world`` average
    and any fp8 dynamic scale are applied in fp32 before one cast on
    send, and the reduced result returns to the leaf's dtype immediately
    after. tp-sharded leaves' compiler-inserted psums are untouched
    (those carry activations' cotangents, not the gradient exchange).
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..ops.fusion import _wire_applies, _wire_sum, resolve_wire_dtype

    wire = resolve_wire_dtype(wire_dtype)

    def sync(spec, g):
        leaf_axes = {ax for s in spec if s
                     for ax in ((s,) if isinstance(s, str) else s)}
        over = tuple(a for a in mesh_axes
                     if a not in leaf_axes and a not in skip_axes)
        if over:
            if _wire_applies(g.dtype, wire):
                world = 1
                for a in over:
                    world *= int(lax.axis_size(a))
                g = _wire_sum(g, over, wire, prescale=1.0 / world)
            else:
                g = lax.pmean(g, over)
        if "tp" in leaf_axes and "tp" in mesh_axes:
            g = g / lax.axis_size("tp")
        if "ep" in leaf_axes and "ep" in mesh_axes:
            # An expert's gradient arrives summed over the ep ranks whose
            # tokens it served, each rank's from its own batch's mean loss:
            # the mean over the whole batch is that sum over ep.
            g = g / lax.axis_size("ep")
        return g

    return jax.tree_util.tree_map(sync, specs, grads,
                                  is_leaf=lambda x: isinstance(x, P))
