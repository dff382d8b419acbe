"""Backward-overlapped bucket collectives + low-precision wire formats
(ISSUE 6 tentpole).

The contract under test: with ``overlap=True`` the flax plane's train step
issues one collective per bucket in backward-completion order behind
``optimization_barrier`` pins: the emission order follows the schedule
exactly, and the total collective count equals the non-overlapped plan
(overlap reorders, never adds). The transformer plane goes further by
default (PR 31): each layer's collective stands INSIDE the lowered
backward, before its last matmul.
With ``wire_dtype`` the collectives run in bf16/fp8 with fp32 scales and
fp32 result accumulation (HLO-pinned operand dtypes), training matches
the fp32-wire path within documented tolerance, and ``zero=True``
composes with compression instead of raising.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.ops import fusion


class _MLP(nn.Module):
    """Three equal-width hidden layers: uniform leaf sizes make the greedy
    bucket count independent of visit order, so plan-vs-schedule count
    equality is exact (the acceptance invariant)."""

    @nn.compact
    def __call__(self, x, train=True):
        h = x
        for _ in range(3):
            h = nn.relu(nn.Dense(64)(h))
        return nn.Dense(10)(h)


# Threshold that splits the MLP into several buckets (64x64 fp32 kernels
# are 16 KiB — above it, so they close buckets).
_THRESH = 8000


def _build(overlap=None, wire_dtype=None, zero=False,
           fusion_threshold=_THRESH, guard=None, accum=1, opt=None):
    hvd.init()
    model = _MLP()
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 8)),
        opt or optax.adam(1e-2), zero=zero, wire_dtype=wire_dtype,
        fusion_threshold=fusion_threshold)
    step = training.make_train_step(
        model, dist_opt, donate=False, overlap=overlap,
        guard_nonfinite=guard, accum_steps=accum)
    return state, dist_opt, step


def _batch(rows=16, seed=0, nan_at=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, 8).astype(np.float32)
    if nan_at is not None:
        x[nan_at] = np.nan
    return x, rng.randint(0, 10, (rows,))


def _np_tree(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _lowered_text(step, state, batch):
    return step.lower(state, batch).as_text()


def _compiled_lines(step, state, batch):
    return step.lower(state, batch).compile().as_text().splitlines()


def _bucket_ar_positions(lines):
    """(line index, element count) of every non-scalar all-reduce in the
    compiled module — the gradient bucket collectives (scalar all-reduces
    are the loss/metric pmeans)."""
    out = []
    for i, line in enumerate(lines):
        m = re.search(r"= \S*?f32\[([0-9,]+)\][^=]* all-reduce(?:-start)?\(",
                      line)
        if m:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            out.append((i, n))
    return out


def _last_dot(lines):
    return max(i for i, line in enumerate(lines)
               if re.search(r"= \S+ dot\(", line))


# ---------------------------------------------------------------------------
# Schedule: probe + determinism (ISSUE 6 satellite).
# ---------------------------------------------------------------------------

def test_probe_grad_order_ranks_last_layer_first():
    """A sequential MLP back-propagates its LAST layer first: the probe
    must rank the final Dense's leaves before the first Dense's."""

    def loss(p, x):
        h = x
        for i in range(3):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.sum(h)

    p = {f"w{i}": jnp.zeros((8, 8)) for i in range(3)}
    order = fusion.probe_grad_order(
        lambda q: jax.grad(loss)(q, jnp.ones((4, 8))), p)
    assert order is not None
    # flatten order is w0, w1, w2; completion order is the reverse.
    assert order == (2, 1, 0)


def test_probe_handles_literal_grad_leaves():
    """A leaf the loss never reads lowers its cotangent to a jaxpr Literal
    (unhashable on this jax) — the probe must degrade it to flatten order,
    not crash (review finding: TypeError on `pos.get(Literal)`)."""

    def loss(p):
        return jnp.sum(p["w"] * 2.0)  # p["unused"] never read

    p = {"unused": jnp.float32(1.0), "w": jnp.ones((3,))}
    order = fusion.probe_grad_order(lambda q: jax.grad(loss)(q), p)
    assert order is not None
    assert sorted(order) == [0, 1]


def test_schedule_deterministic_and_cached():
    """Same (shapes, dtypes, threshold, grad-order) -> identical bucket
    order, served from cache — the cross-process determinism the emission
    chain relies on (every SPMD replica derives the same schedule from
    the same traced program)."""
    leaves = [jnp.zeros((n,), jnp.float32) for n in (100, 200, 300, 400)]
    order = (3, 2, 1, 0)
    first = fusion.plan_schedule(leaves, order, fusion_threshold=1 << 11)
    hits = fusion._schedule_cached.cache_info().hits
    again = fusion.plan_schedule(leaves, order, fusion_threshold=1 << 11)
    assert again == first
    assert fusion._schedule_cached.cache_info().hits == hits + 1
    # Buckets walk the completion order, not flatten order.
    assert first.buckets[0][0] == 3
    # A different order is a different schedule, not a stale hit.
    other = fusion.plan_schedule(leaves, (0, 1, 2, 3),
                                 fusion_threshold=1 << 11)
    assert other.buckets != first.buckets


def test_env_threshold_flip_invalidates_schedule(monkeypatch):
    leaves = [jnp.zeros((8,)), jnp.zeros((8,))]
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "0")
    assert fusion.plan_schedule(leaves, (1, 0)).buckets == ((1,), (0,))
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", str(1 << 20))
    assert fusion.plan_schedule(leaves, (1, 0)).buckets == ((1, 0),)


def test_plan_schedule_rejects_non_permutation():
    leaves = [jnp.zeros((8,)), jnp.zeros((8,))]
    with pytest.raises(ValueError, match="permutation"):
        fusion.plan_schedule(leaves, (0, 0))


def test_zero_emit_order_is_readiness_sorted_and_membership_free():
    """ZeRO overlap reorders EMISSION only: the plan (sharded-state layout,
    checkpoint canonical form) is untouched."""
    params = {"a": jnp.zeros((16,)), "b": jnp.zeros((16,)),
              "c": jnp.zeros((16,))}
    plan = fusion.plan_zero(params, 8, fusion_threshold=0)
    # Backward completes c, b, a (reverse flatten): bucket order follows.
    emit = fusion.zero_emit_order(plan, (2, 1, 0))
    assert emit == (2, 1, 0)
    assert fusion.zero_emit_order(plan, None) == (0, 1, 2)
    # Same plan object either way — membership is pinned.
    assert plan.buckets == ((0,), (1,), (2,))


# ---------------------------------------------------------------------------
# HLO pins: counts, placement, emission order (acceptance criteria).
# ---------------------------------------------------------------------------

def _lm_step_text(overlap, layers=3):
    """Lowered text of the transformer family's dp = 4 step at toy widths
    (``make_parallel_train_step``), where the default plan reduces each
    layer's bucket inside the backward (PR 31; tests/test_backward_sync.py
    has the plan's own tests)."""
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    from horovod_tpu.parallel.transformer import (TransformerConfig,
                                                  make_parallel_train_step)
    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=2, d_ff=64,
                            n_layers=layers, attn_backend="xla",
                            dtype=jnp.float32)
    mesh = create_hybrid_mesh(devices=jax.devices()[:4], dp=4)
    init_state, step = make_parallel_train_step(cfg, mesh, optax.sgd(0.1),
                                                overlap=overlap)
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tok = jnp.zeros((8, 16), jnp.int32)
    return step.lower(params, opt_state, tok, tok).as_text()


def test_overlap_keeps_collective_count():
    """Overlap reorders, never adds. On the flax plane (PR 6's emission)
    the lowered collective counts are equal with and without overlap, and
    the compiler may combine further but never splits. On the transformer
    plane the default plan issues one collective per layer inside the
    backward (every leaf of a toy layer shares one operand) and the old
    plan's for what is left: every leaf is reduced once, none twice."""
    state, _, plain = _build(overlap=None)
    _, _, over = _build(overlap=True)
    b = _batch()
    n_plain = len(re.findall(r"\ball_reduce\b",
                             _lowered_text(plain, state, b)))
    low = over.lower(state, b)
    n_over = len(re.findall(r"\ball_reduce\b", low.as_text()))
    assert n_over == n_plain
    n_compiled = len(re.findall(r" all-reduce(?:-start)?\(",
                                low.compile().as_text()))
    assert 1 <= n_compiled <= n_over
    layers = 3
    old = len(re.findall(r"stablehlo\.all_reduce", _lm_step_text(False)))
    new = len(re.findall(r"stablehlo\.all_reduce", _lm_step_text(None)))
    assert old == 2                    # one fused bucket, and the loss
    assert new == layers + 2 + 1       # layers, embedding, final norm, loss


def test_overlap_schedules_buckets_before_last_backward_op():
    """The acceptance pin, on the program itself and not on a scheduler's
    mercy: under the default plan every layer's collective STANDS before
    the last backward matmul of the lowered step, the highest layer's
    first, each behind a barrier that holds the backward's own cotangent;
    the plan that reduces after the backward (``overlap=False``) has no
    gradient collective before it. (The compiled CPU module cannot pin
    this: XLA:CPU merges the all-reduces and elides the barriers; what the
    TPU's compiler does with them is tests/test_overlap.py, slow.)"""
    def positions(text, pattern):
        return [i for i, line in enumerate(text.splitlines())
                if re.search(pattern, line)]
    old = _lm_step_text(False)
    last = max(positions(old, r"stablehlo\.dot_general"))
    assert not [i for i in positions(old, r"stablehlo\.all_reduce")
                if i < last]
    new = _lm_step_text(None)
    last = max(positions(new, r"stablehlo\.dot_general"))
    early = [i for i in positions(new, r"stablehlo\.all_reduce")
             if i < last]
    assert len(early) >= 2, early           # layers 2 and 1; 0's is the tail
    pins = positions(new, r"optimization_barrier %\S+, %\S+, %\S+ :")
    assert len(pins) == 2 and pins[0] < last
    # Each pin stands between two layers' collectives.
    assert early[0] < pins[0] < early[1] < pins[1]


def test_overlap_emission_follows_schedule_order():
    """The barrier chain pins cross-bucket issue order: the LOWERED
    module's bucket all-reduces appear exactly in the schedule's
    completion order (identified by flat element count), with one
    chaining ``optimization_barrier`` between consecutive buckets. (The
    compiled-module print can't pin this on CPU — XLA:CPU elides
    opt-barriers after scheduling; on TPU they survive to fence the
    collective combiner and fix the issue order.)"""
    b = _batch()
    state, _, over = _build(overlap=True)
    # Expected order: rebuild the schedule from the SAME loss/grad builder
    # the step probes.
    vag = training._build_value_and_grad(
        _MLP(), training.cross_entropy_loss, False)
    vag_grads = jax.tree_util.tree_leaves(state.params)
    order = fusion.probe_grad_order(
        lambda p: vag(p, None, jnp.asarray(b[0]), jnp.asarray(b[1]),
                      jax.random.PRNGKey(0))[1], state.params)
    assert order is not None and len(order) == len(vag_grads)
    sched = fusion.plan_schedule(vag_grads, order,
                                 fusion_threshold=_THRESH)
    expect_sizes = [sum(int(np.prod(vag_grads[j].shape)) for j in bucket)
                    for bucket in sched.buckets]
    txt = _lowered_text(over, state, b)
    got_sizes = [_flat_size(t)
                 for t in _op_operand_types(txt, r"all_reduce")
                 if t != "f32"]  # drop the scalar loss pmean
    assert got_sizes == expect_sizes, (got_sizes, expect_sizes)
    assert len(re.findall(r"optimization_barrier", txt)) == \
        len(expect_sizes) - 1


def test_zero_overlap_keeps_plan_and_counts():
    """ZeRO + overlap: same reduce-scatter/all-gather counts as the
    non-overlapped plan, bucket membership identical (the plan IS the
    sharded state layout), scatters emitted in readiness order."""
    b = _batch()
    state, _, plain = _build(zero=True)
    state2, _, over = _build(zero=True, overlap=True)
    assert state.opt_state.plan == state2.opt_state.plan
    nb = len(state.opt_state.plan.buckets)

    def _counts(step, st):
        txt = _lowered_text(step, st, b)
        return (len(re.findall(r"\breduce_scatter\b", txt)),
                len(re.findall(r"\ball_gather\b", txt)),
                len(re.findall(r"\ball_reduce\b", txt)))

    assert _counts(plain, state) == (nb, nb, 1)
    assert _counts(over, state2) == (nb, nb, 1)


# ---------------------------------------------------------------------------
# Wire formats: HLO dtype pins.
# ---------------------------------------------------------------------------

def _op_operand_types(txt, op):
    """Operand tensor types of every ``op`` application in lowered
    stablehlo text, in trace order. Region-carrying ops (all_reduce,
    reduce_scatter) put the type signature on the region-closing line;
    single-line ops (all_gather) carry it inline — either way it is the
    first ``: (tensor<...>`` after the op name. The ``stablehlo.`` prefix
    keys on applications only (attributes like ``all_gather_dim`` must
    not double-count)."""
    out = []
    for m in re.finditer(r"stablehlo\." + op, txt):
        t = re.search(r":\s*\(tensor<([^>]+)>", txt[m.end():m.end() + 8000])
        if t:
            out.append(t.group(1))
    return out


def _flat_size(mlir_type):
    """Element count of a tensor type string like ``64x64xf32``."""
    n = 1
    for part in mlir_type.split("x")[:-1]:
        n *= int(part)
    return n


def test_bf16_wire_pins_operand_dtype_and_count():
    """Cast-on-send, pattern-pinned: every gradient bucket's all-reduce
    operand is bf16, the count is unchanged vs the fp32 wire (a wire cast
    must never merge or split buckets), and the loss pmean stays f32."""
    b = _batch()
    state, _, plain = _build()
    state, _, wired = _build(wire_dtype="bf16")
    txt_plain = _lowered_text(plain, state, b)
    txt = _lowered_text(wired, state, b)
    n = len(re.findall(r"\ball_reduce\b", txt_plain))
    assert len(re.findall(r"\ball_reduce\b", txt)) == n
    types = _op_operand_types(txt, r"all_reduce")
    assert len(types) == n
    bf16 = [t for t in types if t.endswith("xbf16")]
    # All bucket collectives ride bf16; the scalar loss pmean stays f32.
    assert len(bf16) == n - 1, types


def test_bf16_wire_zero_scatter_dtype_pinned():
    """ZeRO plane: every reduce-scatter operand rides bf16; the update
    all-gather stays full precision (replicas must end bit-identical)."""
    b = _batch()
    state, _, step = _build(zero=True, wire_dtype="bf16")
    txt = _lowered_text(step, state, b)
    nb = len(state.opt_state.plan.buckets)
    rs_types = _op_operand_types(txt, r"reduce_scatter")
    assert len(rs_types) == nb
    assert all(t.endswith("xbf16") for t in rs_types), rs_types
    ag_types = _op_operand_types(txt, r"all_gather")
    assert len(ag_types) == nb
    assert all(t.endswith("xf32") for t in ag_types), ag_types


def test_fp8_wire_adds_exactly_one_pmax_per_bucket():
    """fp8's dynamic scale needs a world-consistent per-bucket amax: one
    scalar pmax per bucket is the ONLY collective any wire format adds
    (documented in docs/performance.md)."""
    b = _batch()
    state, _, plain = _build()
    n_plain = len(re.findall(r"\ball_reduce\b",
                             _lowered_text(plain, state, b)))
    state, _, f8 = _build(wire_dtype="fp8")
    txt = _lowered_text(f8, state, b)
    n_buckets = n_plain - 1  # minus the loss pmean
    assert len(re.findall(r"\ball_reduce\b", txt)) == n_plain + n_buckets
    types = _op_operand_types(txt, r"all_reduce")
    assert sum(t.endswith("xf8E4M3FN") for t in types) == n_buckets, types


# ---------------------------------------------------------------------------
# Parity: low-precision wire vs fp32 wire, both planes.
# ---------------------------------------------------------------------------

def _run(step, state, steps=4):
    losses = []
    for i in range(steps):
        state, m = step(state, _batch(seed=i))
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("mode", ["allreduce", "zero"])
def test_bf16_wire_matches_fp32_within_tolerance(mode):
    """Documented tolerance (docs/performance.md): bf16 wire loses only
    the one quantization on send (scales and accumulation are fp32), so
    a few training steps track the fp32-wire run to bf16 resolution."""
    zero = mode == "zero"
    state_r, _, step_r = _build(zero=zero)
    state_w, _, step_w = _build(zero=zero, wire_dtype="bf16")
    state_r, loss_r = _run(step_r, state_r)
    state_w, loss_w = _run(step_w, state_w)
    np.testing.assert_allclose(loss_w, loss_r, rtol=5e-3)
    # Params: adam scales each step by lr regardless of grad magnitude, so
    # a wire-resolution grad perturbation can move a coordinate by up to
    # ~lr per step before momentum smooths it — tolerance is steps x lr
    # (4 x 1e-2), the bound docs/performance.md documents.
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(state_w.params)),
                     jax.tree_util.tree_leaves(_np_tree(state_r.params))):
        np.testing.assert_allclose(a, b2, rtol=5e-2, atol=4e-2)


@pytest.mark.parametrize("mode", ["allreduce", "zero"])
def test_fp8_wire_matches_fp32_within_tolerance(mode):
    """fp8 e4m3 keeps 3 mantissa bits: coarser, but the dynamic per-bucket
    scale keeps values in range — training stays close over a few steps."""
    zero = mode == "zero"
    state_r, _, step_r = _build(zero=zero)
    state_w, _, step_w = _build(zero=zero, wire_dtype="fp8")
    state_r, loss_r = _run(step_r, state_r)
    state_w, loss_w = _run(step_w, state_w)
    np.testing.assert_allclose(loss_w, loss_r, rtol=5e-2)
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(state_w.params)),
                     jax.tree_util.tree_leaves(_np_tree(state_r.params))):
        np.testing.assert_allclose(a, b2, rtol=5e-1, atol=5e-2)


def test_overlap_is_bit_exact_vs_plain_fp32():
    """Overlap only reorders emission (barriers + schedule): with the same
    fp32 wire the training trajectory must agree to float tolerance.
    (Bucket membership changes, so the reduction grouping — and thus the
    last-ulp rounding — may differ; allclose, not bit-equal.)"""
    state_r, _, step_r = _build()
    state_o, _, step_o = _build(overlap=True)
    state_r, loss_r = _run(step_r, state_r)
    state_o, loss_o = _run(step_o, state_o)
    np.testing.assert_allclose(loss_o, loss_r, rtol=1e-6)
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(state_o.params)),
                     jax.tree_util.tree_leaves(_np_tree(state_r.params))):
        np.testing.assert_allclose(a, b2, rtol=1e-5, atol=1e-7)


def test_replicas_bit_identical_after_zero_wire_gather():
    """Acceptance: zero=True + compression/wire keeps replicas
    bit-identical after the update all-gather — every device holds the
    same params bytes."""
    state, _, step = _build(zero=True, wire_dtype="bf16")
    state, _ = _run(step, state, steps=2)
    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = leaf.addressable_shards
        ref = np.asarray(shards[0].data)
        for s in shards[1:]:
            np.testing.assert_array_equal(np.asarray(s.data), ref)


# ---------------------------------------------------------------------------
# Compositions: guard + accum + overlap + wire.
# ---------------------------------------------------------------------------

def test_guard_skip_bit_stable_under_overlap_and_wire():
    """The full stack: a NaN batch leaves params AND the sharded opt state
    bit-unchanged with overlap + bf16 wire armed (the skip decision rides
    the same channels as before — no new collectives, no divergence)."""
    state, _, step = _build(zero=True, overlap=True, wire_dtype="bf16",
                            guard=True)
    before_p = _np_tree(state.params)
    before_o = _np_tree(state.opt_state)
    s2, m = step(state, _batch(nan_at=3))
    assert float(m["bad_step"]) == 1.0
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(s2.params)),
                     jax.tree_util.tree_leaves(before_p)):
        np.testing.assert_array_equal(a, b2)
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(s2.opt_state)),
                     jax.tree_util.tree_leaves(before_o)):
        np.testing.assert_array_equal(a, b2)
    # The next finite batch trains.
    s3, m2 = step(s2, _batch(seed=5))
    assert float(m2["bad_step"]) == 0.0


def test_guard_adds_zero_collectives_with_overlap_and_wire():
    b = _batch()
    state, dist_opt, _ = _build(zero=True, overlap=True, wire_dtype="bf16")
    model = _MLP()

    def _counts(g):
        step = training.make_train_step(model, dist_opt, donate=False,
                                        overlap=True, guard_nonfinite=g)
        txt = _lowered_text(step, state, b)
        return (len(re.findall(r"\breduce_scatter\b", txt)),
                len(re.findall(r"\ball_gather\b", txt)),
                len(re.findall(r"\ball_reduce\b", txt)))

    assert _counts(True) == _counts(False)


def test_accum_composes_with_overlap_and_wire():
    """One scatter per ACCUMULATED step, wire or not, overlapped or not —
    and parity with the replicated fp32 path holds to wire tolerance."""
    state_r, _, step_r = _build(accum=2)
    state_w, _, step_w = _build(accum=2, overlap=True, wire_dtype="bf16")
    b = _batch(rows=32)
    state_r, _ = step_r(state_r, b)
    state_w, _ = step_w(state_w, b)
    # One adam step can move a coordinate by up to ~lr either way under a
    # wire-resolution grad difference: atol spans 2 x lr.
    for a, b2 in zip(jax.tree_util.tree_leaves(_np_tree(state_w.params)),
                     jax.tree_util.tree_leaves(_np_tree(state_r.params))):
        np.testing.assert_allclose(a, b2, rtol=5e-2, atol=2.5e-2)
    txt = _lowered_text(step_w, state_w, b)
    n_plain = len(re.findall(r"\ball_reduce\b",
                             _lowered_text(step_r, state_r, b)))
    assert len(re.findall(r"\ball_reduce\b", txt)) == n_plain


# ---------------------------------------------------------------------------
# Prescale precision (ISSUE 6 satellite): fp32 prescale for sub-fp32
# buckets.
# ---------------------------------------------------------------------------

def test_prescale_applies_in_fp32_for_bf16_buckets():
    """`fused_allreduce(prescale=)` on bf16 leaves must match the fp32
    reference to one final rounding: scale in fp32, cast once at the end.
    The old dtype-cast prescale (bf16(1/3) then bf16 multiply) double-
    rounds and misses for values this test pins."""
    rng = np.random.RandomState(0)
    vals = rng.randn(257).astype(np.float32)
    x = jnp.asarray(vals, jnp.bfloat16)
    p = 1.0 / 3.0
    scaled = fusion._prescale_array(x, p)
    assert scaled.dtype == jnp.bfloat16
    want = (np.asarray(x, np.float32) * np.float32(p)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(scaled), np.asarray(want))
    # And the old behavior provably differs somewhere on this input (the
    # fix is observable, not vacuous).
    old = np.asarray(
        (x * jnp.asarray(p, jnp.bfloat16)))
    assert not np.array_equal(old, np.asarray(want))


def test_prescale_integer_leaves_untouched():
    x = jnp.arange(8, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(fusion._prescale_array(x, 0.5)), np.arange(8))


# ---------------------------------------------------------------------------
# API guards.
# ---------------------------------------------------------------------------

def test_unknown_wire_dtype_raises_eagerly():
    with pytest.raises(ValueError, match="wire_dtype"):
        hvd.DistributedOptimizer(optax.sgd(0.1), wire_dtype="fp16x")


def test_compression_plus_wire_raises_on_allreduce_plane():
    with pytest.raises(ValueError, match="pick one"):
        hvd.DistributedOptimizer(optax.sgd(0.1),
                                 compression=hvd.Compression.bf16,
                                 wire_dtype="bf16")


def test_overlap_requires_distributed_optimizer():
    hvd.init()
    with pytest.raises(ValueError, match="overlap"):
        training.make_train_step(_MLP(), optax.adam(1e-2), overlap=True)


def test_env_defaults_arm_overlap_and_wire(monkeypatch):
    monkeypatch.setenv("HVD_OVERLAP", "1")
    monkeypatch.setenv("HVD_WIRE_DTYPE", "bf16")
    state, dist_opt, step = _build()
    assert getattr(dist_opt.update, "overlap", False) is True
    assert getattr(dist_opt.update, "wire_dtype", None) == "bf16"
    txt = _lowered_text(step, state, _batch())
    assert _op_operand_types(txt, r"all_reduce")
    assert any(t.endswith("xbf16")
               for t in _op_operand_types(txt, r"all_reduce"))
    monkeypatch.delenv("HVD_OVERLAP")
    monkeypatch.delenv("HVD_WIRE_DTYPE")
    _, dist_opt, _ = _build()
    assert getattr(dist_opt.update, "overlap", True) is False
    assert getattr(dist_opt.update, "wire_dtype", None) == "fp32"
