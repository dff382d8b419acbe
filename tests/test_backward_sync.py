"""The default gradient exchange of ``make_parallel_train_step`` wherever a
layer's gradients cross chips (PR 31): each layer's bucket is reduced INSIDE
the backward (``ops/fusion.reduce_in_backward``), chained on the bucket
before it and due before the backward goes on below the next layer; the
optimizer is told which leaves arrive reduced; and the program switches the
compiler's async all-reduce on before the backend starts
(``utils/chips.enable_async_collectives``).

On the CPU's virtual devices: what the lowered program holds and what the
step computes. What the TPU's compiler makes of it is in
``tests/test_overlap.py`` (slow) and ``PERF.md``.
"""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.obs.registry import registry
from horovod_tpu.ops import fusion
from horovod_tpu.parallel.mesh import create_hybrid_mesh
from horovod_tpu.parallel.transformer import (Indexer, TransformerConfig,
                                              make_parallel_train_step)
from horovod_tpu.utils import chips

LAYERS = 3


@pytest.fixture(autouse=True)
def small_leaves(monkeypatch):
    """At toy widths every leaf is under the size up to which leaves share
    an operand; lower it so that a layer has several operands, as at real
    widths: the two norm scales together, each matrix alone."""
    monkeypatch.setattr(fusion, "_SMALL_LEAF_BYTES", 1 << 12)


def lm(**over):
    base = dict(vocab=256, d_model=64, n_heads=4, n_layers=LAYERS, d_ff=128,
                attn_backend="xla", dtype=jnp.float32,
                unembed_dtype=jnp.float32)
    return TransformerConfig(**{**base, **over})


def keye_shaped(**over):
    """The described block of the Keye cell at toy widths: grouped heads,
    q/k norm, RoPE, an indexer, gated experts of which a share is held, an
    untied head."""
    return lm(n_kv_heads=2, d_head=16, qk_norm=True, rope_theta=1e7,
              mlp="swiglu", tied_head=False, n_experts=8, moe_top_k=2,
              moe_renormalize=True, experts_held=4, first_expert=2,
              indexer=Indexer(2, 8, 16), n_layers=2, **over)


def mesh_of(**axes):
    n = int(np.prod(list(axes.values())))
    return create_hybrid_mesh(devices=jax.devices()[:n], **axes)


def batch(rows=8, T=32, seed=0):
    tok = np.random.default_rng(seed).integers(0, 256, size=(rows, T + 1),
                                               dtype=np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def build(cfg, mesh, opt=None, **kw):
    init_state, step = make_parallel_train_step(
        cfg, mesh, opt or optax.sgd(0.1), **kw)
    return init_state(jax.random.PRNGKey(0)), step


def train(cfg, mesh, steps=3, opt=None, **kw):
    (params, opt_state), step = build(cfg, mesh, opt, **kw)
    tokens, labels = batch()
    losses = []
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses.append(float(loss))
    return jax.device_get(params), losses


def lowered(cfg, mesh, **kw):
    (params, opt_state), step = build(cfg, mesh, **kw)
    return step.lower(params, opt_state, *batch()).as_text()


def ops(text, pattern):
    """Line numbers of the lowered text's ops that match."""
    return [i for i, line in enumerate(text.splitlines())
            if re.search(pattern, line)]


def operands_per_layer(cfg, mesh):
    from horovod_tpu.parallel.transformer import init_params, param_specs
    from jax.sharding import PartitionSpec as P
    layer = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                               cfg))["layers"][0]
    syncs = fusion.plan_grad_sync(
        jax.tree_util.tree_leaves(param_specs(cfg, mesh)["layers"][0],
                                  is_leaf=lambda x: isinstance(x, P)), mesh)
    return len([m for m in fusion._backward_operands(
        jax.tree_util.tree_leaves(layer), syncs) if syncs[m[0]].psum])


# ---------------------------------------------------------------------------
# What the lowered program holds.
# ---------------------------------------------------------------------------

def test_each_layers_bucket_is_reduced_inside_the_backward():
    """dp = 4: every layer's collectives stand among the backward's matmuls,
    highest layer first, and only the rest (embedding and final norm, and
    the loss) after the last of them."""
    cfg, mesh = lm(), mesh_of(dp=4)
    text = lowered(cfg, mesh)
    per_layer = operands_per_layer(cfg, mesh)
    reduces = ops(text, r"stablehlo\.all_reduce")
    last_dot = max(ops(text, r"stablehlo\.dot_general"))
    inside = [i for i in reduces if i < last_dot]
    assert len(inside) >= (LAYERS - 1) * per_layer
    # No leaf is reduced twice: the layers' operands, what is left (the
    # embedding, the final norm), and the loss.
    assert per_layer == 5
    assert len(reduces) == LAYERS * per_layer + 3
    # In the order of the backward: bucket 0 is the highest layer's.
    names = re.findall(r"allreduce\.bucket(\d+)/psum", lowered_names(cfg,
                                                                      mesh))
    assert [int(n) for n in names] == sorted(int(n) for n in names)
    assert set(int(n) for n in names) == set(range(LAYERS + 2))


def lowered_names(cfg, mesh, **kw):
    (params, opt_state), step = build(cfg, mesh, **kw)
    return step.lower(params, opt_state, *batch()).as_text(debug_info=True)


def test_a_bucket_is_due_before_the_backward_of_the_layer_two_below():
    """Bucket k's first operand shares ONE barrier with the cotangent that
    leaves its layer and with the last result of bucket k - 1: so bucket
    k - 1 (the layer above's) is done before the backward goes on into the
    layer below, a layer's backward after its operands were complete."""
    cfg, mesh = lm(), mesh_of(dp=4)
    text = lowered(cfg, mesh).splitlines()
    three = [i for i, line in enumerate(text)
             if re.search(r"optimization_barrier %\S+, %\S+, %\S+ :", line)]
    assert len(three) == LAYERS - 1          # nothing precedes bucket 0
    reduces = ops("\n".join(text), r"stablehlo\.all_reduce")
    dots = ops("\n".join(text), r"stablehlo\.dot_general")
    per_layer = operands_per_layer(cfg, mesh)
    for n, at in enumerate(three):
        # The barrier holds its first operand (the two norm scales), an
        # activation's cotangent as [B * T, d] (no [B, T, d]: the TPU's
        # layout assignment must get no choice there) and the carry (the
        # layer above's last result, wqkv's)...
        types = re.findall(r"tensor<([0-9x]+)xf(?:32|64)>",
                           text[at].split(" : ")[1])
        assert types == ["128", "64x64", "64x192"], text[at]
        # ...stands after every collective of the bucket before it...
        before = [i for i in reduces if i < at]
        assert len(before) == (n + 1) * per_layer
        # ...and the backward's matmuls of the layer below come after it
        # (below the lowest layer there is only the embedding).
        assert n == LAYERS - 2 or any(d > at for d in dots)
    # Within a bucket every operand waits for the result before it.
    two = [line for line in text
           if re.search(r"optimization_barrier %\S+, %\S+ :", line)]
    assert len(two) == LAYERS * (per_layer - 1)


@pytest.mark.parametrize("which", ["lm", "keye_shaped", "resnet"])
def test_one_member_on_the_sync_axis_leaves_the_step_as_it_was(which):
    """dp = 1: nothing crosses chips, no bucket exists, and the lowered
    step is ``overlap=False``'s text, character for character."""
    if which == "resnet":
        from horovod_tpu import models, training
        hvd.shutdown()
        hvd.init(devices=jax.devices()[:1])
        try:
            texts = []
            for overlap in (None, False):
                model = models.resnet50(num_classes=10, dtype=jnp.float32)
                state, dist_opt = training.create_train_state(
                    model, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)),
                    optax.sgd(0.1), overlap=overlap)
                step = training.make_train_step(model, dist_opt,
                                                overlap=overlap)
                texts.append(step.lower(
                    state, (jnp.zeros((2, 32, 32, 3)),
                            jnp.zeros((2,), jnp.int32))).as_text())
        finally:
            hvd.shutdown()
            hvd.init()
        assert texts[0] == texts[1]
        return
    cfg = lm() if which == "lm" else keye_shaped()
    mesh = mesh_of(dp=1)
    assert lowered(cfg, mesh) == lowered(cfg, mesh, overlap=False)
    assert which != "lm" or "optimization_barrier" not in lowered(cfg, mesh)


@pytest.mark.parametrize("kw", [dict(zero=True), dict(accum_steps=2), {}])
def test_the_plans_that_reduce_after_the_backward_keep_doing_so(kw):
    """ZeRO's reduce-scatter and microbatch accumulation (one exchange per
    accumulated step) lower to what they lower to under ``overlap=False``,
    which has no collective of a layer's gradients among the backward's
    matmuls and no barrier."""
    cfg, mesh = lm(), mesh_of(dp=4)
    text = lowered(cfg, mesh, overlap=False, **kw)
    if kw:
        assert lowered(cfg, mesh, **kw) == text
        return
    last_dot = max(ops(text, r"stablehlo\.dot_general"))
    assert not [i for i in ops(text, r"stablehlo\.all_reduce")
                if i < last_dot]
    assert "optimization_barrier" not in text


# ---------------------------------------------------------------------------
# What the step computes.
# ---------------------------------------------------------------------------

def close(a, b, atol=2e-6):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, atol=atol, err_msg=str(path))


@pytest.mark.parametrize("axes", [dict(dp=4), dict(dp=2, tp=2)])
def test_three_steps_equal_the_plan_that_reduces_after_the_backward(axes):
    """The reduced gradients are the old plan's to float32 rounding: same
    sums, same wire, another order of issue."""
    cfg, mesh = lm(), mesh_of(**axes)
    p_new, l_new = train(cfg, mesh, opt=optax.adamw(1e-2))
    p_old, l_old = train(cfg, mesh, opt=optax.adamw(1e-2), overlap=False)
    assert l_new == pytest.approx(l_old, rel=1e-6)
    assert l_new[-1] < l_new[0]
    close(p_new, p_old)


def test_overlap_true_stays_accepted_and_means_the_same():
    cfg, mesh = lm(), mesh_of(dp=4)
    assert lowered(cfg, mesh, overlap=True) == lowered(cfg, mesh)


def test_dp4_equals_dp1():
    cfg = lm()
    p4, l4 = train(cfg, mesh_of(dp=4))
    p1, l1 = train(cfg, mesh_of(dp=1))
    assert l4 == pytest.approx(l1, rel=1e-5)
    close(p4, p1)


def test_the_described_block_rides_the_same_plan():
    """Experts, indexer, grouped heads under dp = 2: the same code reduces
    their layers in the backward, and the step equals the old plan's."""
    cfg, mesh = keye_shaped(), mesh_of(dp=2)
    text = lowered(cfg, mesh)
    assert len([i for i in ops(text, r"stablehlo\.all_reduce")
                if i < max(ops(text, r"stablehlo\.dot_general"))]) >= 1
    p_new, _ = train(cfg, mesh, steps=2, aux_weight=0.0)
    p_old, _ = train(cfg, mesh, steps=2, aux_weight=0.0, overlap=False)
    close(p_new, p_old)


def test_the_wire_dtype_rides_the_backward_buckets():
    """``wire_dtype="bf16"`` puts the layers' operands on the wire in
    bf16 as it does the rest's, and trains within the wire's tolerance."""
    cfg, mesh = lm(), mesh_of(dp=4)
    text = lowered(cfg, mesh, wire_dtype="bf16")
    kinds = re.findall(
        r"stablehlo\.all_reduce.*?\(tensor<[0-9x]*x?(bf16|f32)>\) ->", text,
        flags=re.S)
    assert kinds.count("bf16") == LAYERS * operands_per_layer(cfg, mesh) + 2
    p_wire, _ = train(cfg, mesh, wire_dtype="bf16")
    p_full, _ = train(cfg, mesh)
    close(p_wire, p_full, atol=2e-3)


def test_the_guard_sees_the_leaves_the_backward_reduced():
    """``guard_nonfinite``: a NaN that reaches only a layer's gradients
    (reduced in the backward, handed to the optimizer as done) still skips
    the step on every replica."""
    cfg, mesh = lm(), mesh_of(dp=4)
    (params, opt_state), step = build(cfg, mesh, guard_nonfinite=True)
    tokens, labels = batch()
    good = jax.device_get(params)
    new, _, loss = step(params, opt_state, tokens, labels)
    assert np.isfinite(float(loss)) and float(loss) > 0
    assert not np.array_equal(jax.device_get(new)["layers"][0]["w1"],
                              good["layers"][0]["w1"])
    bad = jax.tree_util.tree_map(lambda x: x, params)
    bad["layers"][1]["ln2"] = bad["layers"][1]["ln2"] * jnp.nan
    kept, _, loss = step(bad, opt_state, tokens, labels)
    assert float(loss) == 0.0
    np.testing.assert_array_equal(jax.device_get(kept)["layers"][0]["w1"],
                                  good["layers"][0]["w1"])


# ---------------------------------------------------------------------------
# The counter and the start-up helper.
# ---------------------------------------------------------------------------

def bucket_counts():
    return {labels["where"]: value
            for name, labels, value in registry().collect()[1]
            if name == "hvd_grad_sync_buckets_total"}


def test_the_counter_says_where_the_plan_issued_each_bucket():
    cfg = lm()
    before = bucket_counts()
    lowered(cfg, mesh_of(dp=4))
    mid = bucket_counts()
    assert mid.get("backward", 0) - before.get("backward", 0) == LAYERS
    assert mid.get("after", 0) - before.get("after", 0) == 2
    lowered(cfg, mesh_of(dp=4), overlap=False)
    after = bucket_counts()
    assert after["backward"] == mid["backward"]
    assert after["after"] > mid["after"]


def test_the_start_up_helper_merges_and_leaves_the_users_options(monkeypatch):
    args = list(chips.ASYNC_ALLREDUCE_ARGS)
    monkeypatch.setattr(chips, "_tpu_backend_is_up", lambda: False)
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    assert chips.enable_async_collectives()
    assert chips.os.environ["LIBTPU_INIT_ARGS"].split() == args
    # Again: nothing is added twice.
    assert chips.enable_async_collectives()
    assert chips.os.environ["LIBTPU_INIT_ARGS"].split() == args
    # A user's value of one of them stays, whatever it says; the user's
    # other options keep their place.
    off = args[0].replace("=true", "=false")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", f"--xla_foo=1 {off}")
    assert chips.enable_async_collectives()
    assert chips.os.environ["LIBTPU_INIT_ARGS"].split() == [
        "--xla_foo=1", off] + args[1:]


def test_the_start_up_helper_warns_once_the_backend_is_up(monkeypatch):
    monkeypatch.setattr(chips, "_tpu_backend_is_up", lambda: True)
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_foo=1")
    with pytest.warns(RuntimeWarning, match="after the TPU backend"):
        assert not chips.enable_async_collectives()
    assert chips.os.environ["LIBTPU_INIT_ARGS"] == "--xla_foo=1"
    # With every option there already, there is nothing to warn about.
    monkeypatch.setenv("LIBTPU_INIT_ARGS",
                       " ".join(chips.ASYNC_ALLREDUCE_ARGS))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert chips.enable_async_collectives()


def test_importing_the_package_set_the_options():
    """``import horovod_tpu`` (above) ran the helper before any backend
    could start; on this CPU-only process no TPU backend is up."""
    assert not chips._tpu_backend_is_up()
    have = chips.os.environ.get("LIBTPU_INIT_ARGS", "").split()
    names = {a.split("=", 1)[0] for a in have}
    assert {a.split("=", 1)[0] for a in chips.ASYNC_ALLREDUCE_ARGS} <= names
