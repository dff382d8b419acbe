"""The described block at a small size on the CPU: sparse attention over
indexer-selected keys with grouped key/value heads, RoPE and q/k norm, and
the expert layer that holds a share of its experts — against the plain
reference ``benchmarks/reference/lm_moe_dsa.py`` (forward, loss, gradients
of every leaf), and the properties the equations promise: exact top-k with
ties to the smaller index, nothing dropped under imbalance, the shares of
an expert-parallel group adding up, ``ep`` = 2 equal to ``ep`` = 1."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import lm_moe_dsa as reference  # noqa: E402

from horovod_tpu.ops import pallas_sparse_attention as kernels  # noqa: E402
from horovod_tpu.ops import sparse_attention as sa  # noqa: E402
from horovod_tpu.parallel import create_hybrid_mesh, moe_ffn  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    Indexer, TransformerConfig, dense_nll, forward_with_stats, init_params,
    make_parallel_train_step)

V, D, E, F = 96, 64, 8, 32


def toy(**over):
    """2 layers, hidden 64, 4/2 heads of 16, 8 experts top-2 of which 4 are
    held from expert 2 on, indexer 2 x 8, topk 16."""
    base = dict(vocab=V, d_model=D, n_heads=4, n_kv_heads=2, d_head=16,
                n_layers=2, d_ff=F, n_experts=E, moe_top_k=2,
                moe_renormalize=True, experts_held=4, first_expert=2,
                qk_norm=True, rope_theta=1e7, mlp="swiglu", tied_head=False,
                indexer=Indexer(2, 8, 16), dtype=jnp.float32,
                attn_backend="xla", unembed_dtype=jnp.float32)
    return TransformerConfig(**{**base, **over})


def sizes(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                d_head=cfg.d_head, idx_heads=cfg.indexer.n_heads,
                idx_dim=cfg.indexer.d_head, topk=cfg.indexer.topk,
                experts_per_tok=cfg.moe_top_k, first_expert=cfg.first_expert,
                rope_theta=cfg.rope_theta)


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(T=64, B=2, seed=0):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def system_loss(params, tokens, labels, cfg):
    logits, layers = forward_with_stats(params, tokens, cfg,
                                        one_device_mesh())
    nll = dense_nll(logits, labels)
    kl = jnp.stack([e["kl"] for e in layers])
    # The sum over a sequence's rows is what the loss differentiates.
    kl_term = jnp.sum(jnp.stack([e["kl_sum"] for e in layers])) / kl.size
    return jnp.mean(nll) + kl_term, (nll, kl, layers, kl_term)


@pytest.fixture(scope="module", params=["share", "all"])
def trained_pair(request):
    """System and reference, forward and gradients, on the same weights:
    holding a share of the experts (4 of 8, from expert 2), and all."""
    share = request.param == "share"
    cfg = toy() if share else toy(experts_held=E, first_expert=0)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_params(jax.random.PRNGKey(0), cfg))
    tokens, labels = batch()
    with jax.default_matmul_precision("highest"):
        (loss, (nll, kl, layers, _)), grads = jax.value_and_grad(
            system_loss, has_aux=True)(params, tokens, labels, cfg)
    want = reference.forward(params, tokens, labels, sizes(cfg), q_block=32)
    want_grads = jax.grad(lambda p: reference.forward(
        p, tokens, labels, sizes(cfg), q_block=32)["loss"])(params)
    return dict(loss=loss, nll=nll, kl=kl, layers=layers, grads=grads,
                want=want, want_grads=want_grads, share=share)


def test_forward_and_loss_match_the_reference(trained_pair):
    t = trained_pair
    np.testing.assert_allclose(t["nll"], t["want"]["nll"], atol=2e-5)
    np.testing.assert_allclose(t["kl"], t["want"]["kl"], atol=2e-6)
    np.testing.assert_allclose(t["loss"], t["want"]["loss"], rtol=1e-6)
    for layer, selected in zip(t["layers"], t["want"]["selected"]):
        assert bool(jnp.all((layer["mask"] != 0) == selected))


LEAVES = sorted({re.sub(r"\d+", "*", jax.tree_util.keystr(path))
                 for path, _ in jax.tree_util.tree_leaves_with_path(
                     init_params(jax.random.PRNGKey(0), toy(n_layers=1)))})


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(trained_pair, leaf):
    got = jax.tree_util.tree_leaves_with_path(trained_pair["grads"])
    want = jax.tree_util.tree_leaves(trained_pair["want_grads"])
    seen = 0
    for (path, g), w in zip(got, want):
        if re.sub(r"\d+", "*", jax.tree_util.keystr(path)) != leaf:
            continue
        seen += 1
        if leaf.endswith("['router']") and trained_pair["share"]:
            # Experts are absent: the routing weights' gradient needs their
            # outputs, so it is left out and the router is not trained.
            assert not jnp.any(g) and not jnp.any(w)
            continue
        assert float(jnp.abs(w).max()) > 1e-4, "a leaf the loss ignores"
        np.testing.assert_allclose(g, w, atol=2e-6 + 1e-4 * float(
            jnp.abs(w).max()))
    assert seen


def test_the_two_loss_terms_train_disjoint_parameters():
    """The NLL passes no gradient to the indexer (top-k passes none), and
    the KL none to anything else."""
    cfg = toy(n_layers=1)
    params = init_params(jax.random.PRNGKey(1), cfg)
    tokens, labels = batch(seed=1)

    def term(which):
        def f(p):
            _, (nll, _, _, kl_term) = system_loss(p, tokens, labels, cfg)
            return jnp.mean(nll) if which == "nll" else kl_term
        return jax.grad(f)(params)
    for which, grads in (("nll", term("nll")), ("kl", term("kl"))):
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            indexer = "idx_" in jax.tree_util.keystr(path)
            if indexer == (which == "nll"):
                assert float(jnp.abs(g).max()) == 0.0, (which, path)


@pytest.mark.parametrize("topk,r0", [(16, 0), (16, 32), (5, 0), (64, 0)],
                         ids=["below_and_beyond", "later_rows", "small_k",
                              "k_is_T"])
def test_selection_is_exact_top_k_with_ties_to_the_smaller_index(topk, r0):
    """Against ``lax.top_k`` (lower index first among equals) on scores
    full of ties: rows t < topk keep all of their t + 1 keys."""
    R, T = 32, 64
    rng = np.random.default_rng(3)
    scores = jnp.asarray(rng.integers(-3, 4, (2, R, T)) / 2.0, jnp.float32)
    got, _ = sa._select_block(r0, scores, topk)
    causal = np.arange(T)[None, :] <= (r0 + np.arange(R))[:, None]
    for b in range(2):
        want = reference.select(jnp.where(causal, scores[b], -jnp.inf), topk)
        assert bool(jnp.all((got[b] != 0) == want))
    rows = np.asarray(jnp.sum(got != 0, -1))[0]
    np.testing.assert_array_equal(rows, np.minimum(topk,
                                                   r0 + np.arange(R) + 1))


def dense_experts(x, router, w_gate, w_up, w_down, top_k, first):
    """Every held expert on every token, weighted by the renormalised gate
    where the token chose it."""
    layer = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        return reference._experts(x, layer, {"experts_per_tok": top_k,
                                             "first_expert": first})


def expert_weights(rng, held):
    n = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    return n(held, D, F), n(held, D, F), n(held, F, D)


@pytest.mark.parametrize("bias", [0.0, 8.0], ids=["balanced", "one_hot"])
def test_routing_drops_nothing_under_imbalance(bias):
    """A router biased so that one held expert gets every token: the layer
    still equals the dense computation, chunk after chunk (2 of 32 experts
    held: a chunk is a quarter of the rows)."""
    n_experts = 32
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(128, D)), jnp.float32).at[:, 0].set(1.0)
    router = jnp.asarray(rng.normal(size=(D, n_experts)) * 0.1, jnp.float32)
    router = router.at[0, 3].add(bias)
    w_gate, w_up, w_down = expert_weights(rng, 2)
    with jax.default_matmul_precision("highest"):
        y, stats = moe_ffn(x, router, w_up, w_down, w_gate=w_gate, top_k=2,
                           renormalize=True, first_expert=2)
    want = dense_experts(x, router, w_gate, w_up, w_down, 2, 2)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert int(stats["held_load"].sum() + stats["absent"]) == 128 * 2
    if bias:
        # Every token chose expert 3, and the rows held here are more
        # than one chunk holds (four times the balanced load).
        assert int(stats["held_load"][1]) == 128
        assert int(stats["held_load"].sum()) > 4 * 128 * 2 * 2 // n_experts


def test_the_shares_of_a_group_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their shares of the expert output,
    summed, are the layer with all eight experts (attention and the router
    are what every member computes alike, and count once)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)) * 0.1, jnp.float32)
    w_gate, w_up, w_down = expert_weights(rng, E)
    with jax.default_matmul_precision("highest"):
        shares = [moe_ffn(x, router, w_up[c:c + 2], w_down[c:c + 2],
                          w_gate=w_gate[c:c + 2], top_k=2, renormalize=True,
                          first_expert=c)[0] for c in range(0, E, 2)]
    whole = dense_experts(x, router, w_gate, w_up, w_down, 2, 0)
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)


def test_ep2_equals_ep1_holding_both_halves():
    """One SGD step on an ep = 2 mesh (each rank holds half the experts
    and half the batch; tokens all-gathered, shares reduce-scattered)
    against the same step on one device holding all of them."""
    cfg = toy(n_layers=1, experts_held=0, first_expert=0)
    tokens, labels = batch(T=32, B=4, seed=6)
    out = []
    for mesh in (one_device_mesh(),
                 create_hybrid_mesh(ep=2, devices=jax.devices()[:2])):
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.sgd(0.1), aux_weight=0.0)
        params, opt_state = init_state(jax.random.PRNGKey(7))
        with jax.default_matmul_precision("highest"):
            params, _, loss = step(params, opt_state, tokens, labels)
        out.append((float(loss), jax.device_get(params)))
    (loss1, p1), (loss2, p2) = out
    assert loss1 == pytest.approx(loss2, rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p1),
                            jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(a, b, atol=2e-6, err_msg=str(path))


def _force_plan(monkeypatch, plan):
    """Pin the backward's plan (``fused``: ``dsa_bwd``; ``split``:
    ``dsa_bwd_dq`` + ``dsa_bwd_dkv``). ``_bwd`` is jitted, so traces made
    under another plan are dropped first."""
    monkeypatch.setattr(kernels, "_bwd_plan", lambda T, d, G, itemsize: plan)
    jax.clear_caches()


def _plan_count(plan):
    return kernels._plan_counter().labels(plan=plan).value


@pytest.mark.parametrize("Hkv", [2, 4], ids=["group_of_2", "group_of_1"])
@pytest.mark.parametrize("plan", ["fused", "split"])
def test_sparse_kernels_match_the_xla_form(monkeypatch, plan, Hkv):
    """``dsa_fwd`` and both backward plans in interpret mode against the
    row-block XLA form: values, log-sum-exp and the three gradients, over
    three k tiles, with rows that select nothing in their first tiles."""
    _force_plan(monkeypatch, plan)
    B, T, Hq, d = 1, 1536, 4, 128
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, h, d)), jnp.float32)
               for h in (Hq, Hkv, Hkv))
    qi = jnp.asarray(rng.normal(size=(B, T, 2, 8)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(B, T, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, T, 2)), jnp.float32)
    mask, _ = sa.select_topk(qi, ki, w, 100)
    # Rows of the last k tile that keep themselves and what they chose in
    # that tile only: two k tiles pass before their first selected key.
    late = jnp.arange(1100, 1300)
    mask = mask.at[:, 1100:1300, :1024].set(0).at[:, late, late].set(1)
    assert kernels.tilable(T, d) and T // kernels._TK >= 3
    before = _plan_count(plan)

    def run(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v, mask)
            return jnp.sum(o * jnp.cos(o)), (o, lse)
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
    (_, (o1, lse1)), g1 = run(sa._attend_xla)
    (_, (o2, lse2)), g2 = run(kernels.attend)
    np.testing.assert_allclose(o2, o1, atol=1e-5)
    np.testing.assert_allclose(lse2, lse1, atol=1e-5)
    for a, b in zip(g2, g1):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # One tick a trace of the backward, under the label of its plan.
    assert _plan_count(plan) == before + 1
    jax.clear_caches()


@pytest.mark.parametrize("T,d,G,itemsize,plan", [
    (8192, 128, 8, 2, "fused"),        # the Keye cell
    (1024, 128, 1, 4, "fused"),        # the tests' float32 shapes
    (15360, 128, 8, 2, "fused"),       # the last T that fits at G 8, bf16
    (15872, 128, 8, 2, "split"),
    (8192, 128, 8, 4, "fused"),
    (12288, 128, 8, 4, "split"),       # float32 doubles the residents
    (32768, 128, 1, 2, "split"),
])
def test_backward_plan_follows_the_shape(T, d, G, itemsize, plan):
    """The plan is a function of (T, d, G, itemsize) alone: ``fused``
    while the whole-sequence residents fit ``_VMEM_LIMIT``, ``split``
    beyond (tests/test_tpu_compile.py holds the sum to the compiler)."""
    assert kernels._bwd_plan(T, d, G, itemsize) == plan


@pytest.mark.parametrize("overlap", [False, None])
def test_dense_step_lowers_to_the_same_collectives_and_matmuls_as_before(
        overlap):
    """The dense block goes through the described block's code and lowers
    to what it lowered to before it (counts taken on the parent commit of
    PR 28, same configuration): 41 matmuls, and 2 all-reduces under the plan
    that reduces after the backward. The default plan (PR 31) reduces each
    of the 2 layers inside the backward, operand by operand, and leaves the
    embedding, the final norm and the loss: the same leaves, none twice."""
    cfg = TransformerConfig(vocab=256, d_model=256, n_heads=2, n_layers=2,
                            d_ff=512, dtype=jnp.bfloat16,
                            attn_backend="pallas",
                            unembed_dtype=jnp.bfloat16)
    mesh = create_hybrid_mesh(devices=jax.devices()[:4], dp=4)
    init_state, step = make_parallel_train_step(cfg, mesh,
                                                optax.adamw(1e-3),
                                                overlap=overlap)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((8, 256), jnp.int32)
    text = step.lower(*state, tok, tok).as_text()
    all_reduces = 2
    if overlap is None:
        from horovod_tpu.ops import fusion
        layer = jax.tree_util.tree_leaves(state[0]["layers"][0])
        sync = fusion.GradSync(("dp",), (), 4)
        all_reduces = 3 + 2 * len(fusion._backward_operands(
            layer, [sync] * len(layer)))
    assert len(re.findall(r"stablehlo\.all_reduce", text)) == all_reduces
    assert len(re.findall("dot_general", text)) == 41
    assert "all_gather" not in text and "all_to_all" not in text


def test_serving_and_pipeline_refuse_the_described_block():
    from horovod_tpu.parallel.transformer import init_kv_cache
    with pytest.raises(NotImplementedError, match="dense block"):
        init_kv_cache(toy(n_experts=0, experts_held=0, first_expert=0),
                      2, 16)
    with pytest.raises(NotImplementedError, match="dense FFNs"):
        init_kv_cache(toy(), 2, 16)


def test_grouped_kernel_matches_ragged_dot():
    """The TPU's grouped product (jax's Pallas gmm/tgmm behind one custom
    VJP with tiles chosen per product) in interpret mode against
    ``lax.ragged_dot``: values on the rows of the groups, and both
    gradients, with an empty group and rows of no group at the end."""
    from horovod_tpu.parallel import moe
    rng = np.random.default_rng(9)
    m, k, n = 1024, 256, 128
    rows = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, k, n)) * 0.1, jnp.float32)
    sizes = jnp.asarray([300, 0, 500], jnp.int32)
    real = (jnp.arange(m) < 800)[:, None]

    def run(fn):
        def loss(rows, w):
            out = jnp.where(real, fn(rows, w, sizes), 0)
            return jnp.sum(out * jnp.sin(out)), out
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(rows, w)
    # x64 off, as on the chip: jax's kernel mixes index widths under it.
    with jax.default_matmul_precision("highest"), jax.enable_x64(False):
        (_, out1), (d_rows1, d_w1) = run(lax.ragged_dot)
        (_, out2), (d_rows2, d_w2) = run(moe._grouped_kernel)
    np.testing.assert_allclose(out2, out1, atol=1e-4)
    np.testing.assert_allclose(jnp.where(real, d_rows2, 0),
                               jnp.where(real, d_rows1, 0), atol=1e-4)
    np.testing.assert_allclose(d_w2, d_w1, atol=1e-3)
