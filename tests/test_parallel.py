"""Multi-axis parallelism tests on the 8-device CPU mesh: each sharded
implementation is checked against a dense single-device reference computed
on the gathered data (algebraic-identity style, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import parallel
from horovod_tpu.parallel import (
    TransformerConfig,
    create_hybrid_mesh,
    gpipe,
    make_parallel_train_step,
    make_pp_transformer_train_step,
    moe_ffn,
    one_f_one_b,
    ring_attention,
    ulysses_attention,
)


def _dense_attention(q, k, v, causal):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    if causal:
        t = q.shape[1]
        pos = jnp.arange(t)
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        B, T, H, D, S = 2, 16, 4, 8, 4
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                   for _ in range(3))
        expected = _dense_attention(q, k, v, causal)

        mesh = create_hybrid_mesh(sp=S, devices=jax.devices()[:S])
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=causal),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = f(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_matches_dense(self, causal):
        B, T, H, D, S = 2, 16, 4, 8, 4
        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                   for _ in range(3))
        expected = _dense_attention(q, k, v, causal)

        mesh = create_hybrid_mesh(sp=S, devices=jax.devices()[:S])
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                              causal=causal),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = f(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-4, atol=2e-5)


class TestTensorParallel:
    def test_column_row_pair_matches_dense(self):
        """column @ row with psum == the unsharded two-layer matmul."""
        D, F, S = 8, 16, 4
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3, D), jnp.float32)
        w1 = jnp.asarray(rng.randn(D, F), jnp.float32)
        w2 = jnp.asarray(rng.randn(F, D), jnp.float32)
        expected = (x @ w1) @ w2

        mesh = create_hybrid_mesh(tp=S, devices=jax.devices()[:S])
        f = jax.jit(jax.shard_map(
            lambda x, w1, w2: parallel.row_parallel(
                parallel.column_parallel(x, w1), w2, axis_name="tp"),
            mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp", None)),
            out_specs=P(), check_vma=False))
        np.testing.assert_allclose(np.asarray(f(x, w1, w2)),
                                   np.asarray(expected), rtol=1e-4)


class TestMoE:
    def test_tokens_routed_and_transformed(self):
        T, D, F, E = 16, 8, 16, 4
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(E * T, D), jnp.float32)
        gate = jnp.asarray(rng.randn(D, E), jnp.float32)
        w1 = jnp.asarray(rng.randn(E, D, F), jnp.float32) * 0.1
        w2 = jnp.asarray(rng.randn(E, F, D), jnp.float32) * 0.1

        mesh = create_hybrid_mesh(ep=E, devices=jax.devices()[:E])
        f = jax.jit(jax.shard_map(
            lambda x, g, w1, w2: (lambda y, stats: (y, stats["aux"]))(
                *moe_ffn(x, g, w1, w2, top_k=1, axis_name="ep")),
            mesh=mesh,
            in_specs=(P("ep"), P(), P("ep", None, None),
                      P("ep", None, None)),
            out_specs=(P("ep"), P()), check_vma=False))
        y, aux = f(x, gate, w1, w2)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert float(aux) > 0

        # Reference: each token goes through its argmax expert's FFN
        # scaled by the gate prob (nothing is dropped).
        probs = jax.nn.softmax(x @ gate, axis=-1)
        eidx = jnp.argmax(probs, axis=-1)
        expected = []
        for i in range(x.shape[0]):
            e = int(eidx[i])
            h = jax.nn.gelu(x[i] @ w1[e])
            expected.append((h @ w2[e]) * probs[i, e])
        np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                                   rtol=2e-4, atol=2e-5)


class TestPipeline:
    def test_gpipe_matches_sequential(self):
        """4-stage pipeline over microbatches == applying all 4 stage
        functions in order on each microbatch."""
        S, M, mb, D = 4, 6, 3, 8
        rng = np.random.RandomState(0)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        expected = x
        for s in range(S):
            expected = jnp.tanh(expected @ ws[s])

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])
        f = jax.jit(jax.shard_map(
            lambda w, x: gpipe(stage_fn, w[0], x, axis_name="pp"),
            mesh=mesh, in_specs=(P("pp", None, None), P()),
            out_specs=P(), check_vma=False))
        out = f(ws, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-6)

    def test_gpipe_differentiable(self):
        S, M, mb, D = 4, 4, 2, 4
        rng = np.random.RandomState(1)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])

        def loss_fn(w_local, x):
            out = gpipe(lambda w, a: jnp.tanh(a @ w), w_local[0], x,
                        axis_name="pp")
            # Sum-of-squares loss; pmean for identical value on all stages.
            return jax.lax.pmean(jnp.mean(out * out), "pp")

        g = jax.jit(jax.shard_map(
            jax.grad(loss_fn), mesh=mesh,
            in_specs=(P("pp", None, None), P()),
            out_specs=P("pp", None, None), check_vma=False))(ws, x)
        assert g.shape == ws.shape
        # Every stage's weight must receive gradient signal.
        norms = np.asarray(jnp.sum(jnp.abs(g), axis=(1, 2)))
        assert (norms > 0).all(), norms


class TestOneFOneB:
    """1F1B-style memory-bounded pipeline training: loss and EVERY stage's
    parameter gradients must match sequential autodiff exactly (the
    schedule only reorders work; recompute-in-VJP must not change math)."""

    def _run(self, S, M, mb=3, D=8, seed=0):
        rng = np.random.RandomState(seed)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)
        y = jnp.asarray(rng.randn(M, mb, D), jnp.float32)

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        def loss_fn(act, yy):
            return jnp.mean((act - yy) ** 2)

        def full_loss(ws_all):
            total = 0.0
            for m in range(M):
                a = x[m]
                for s in range(S):
                    a = jnp.tanh(a @ ws_all[s])
                total = total + loss_fn(a, y[m])
            return total / M

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])

        def wrapped(w, xx, yy):
            loss, grads = one_f_one_b(stage_fn, w[0], xx, yy, loss_fn,
                                      axis_name="pp")
            return loss, grads[None]

        f = jax.jit(jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=(P("pp", None, None), P(), P()),
            out_specs=(P(), P("pp", None, None)), check_vma=False))
        loss, grads = f(ws, x, y)
        return (float(loss), np.asarray(grads),
                float(full_loss(ws)), np.asarray(jax.grad(full_loss)(ws)))

    def test_matches_sequential_autodiff(self):
        loss, grads, eloss, egrads = self._run(S=4, M=6)
        np.testing.assert_allclose(loss, eloss, rtol=1e-5)
        np.testing.assert_allclose(grads, egrads, rtol=1e-4, atol=1e-6)

    def test_fewer_microbatches_than_stages(self):
        loss, grads, eloss, egrads = self._run(S=4, M=2, seed=3)
        np.testing.assert_allclose(loss, eloss, rtol=1e-5)
        np.testing.assert_allclose(grads, egrads, rtol=1e-4, atol=1e-6)

    def test_two_stages(self):
        loss, grads, eloss, egrads = self._run(S=2, M=8, seed=5)
        np.testing.assert_allclose(loss, eloss, rtol=1e-5)
        np.testing.assert_allclose(grads, egrads, rtol=1e-4, atol=1e-6)

    def test_bf16_activations(self):
        """The carry buffers must track the activation dtype — bf16
        microbatches (the low-precision large-M regime 1F1B targets) must
        trace and produce finite f32 param grads."""
        S, M, mb, D = 4, 5, 2, 8
        rng = np.random.RandomState(2)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        x = jnp.asarray(rng.randn(M, mb, D), jnp.bfloat16)
        y = jnp.asarray(rng.randn(M, mb, D), jnp.bfloat16)

        def stage_fn(w, a):
            return jnp.tanh(a @ w.astype(jnp.bfloat16))

        def loss_fn(act, yy):
            return jnp.mean(
                (act.astype(jnp.float32) - yy.astype(jnp.float32)) ** 2)

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])

        def wrapped(w, xx, yy):
            loss, grads = one_f_one_b(stage_fn, w[0], xx, yy, loss_fn,
                                      axis_name="pp")
            return loss, grads[None]

        loss, grads = jax.jit(jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=(P("pp", None, None), P(), P()),
            out_specs=(P(), P("pp", None, None)), check_vma=False))(ws, x, y)
        assert np.isfinite(float(loss))
        g = np.asarray(grads, np.float32)
        assert np.isfinite(g).all()
        assert (np.abs(g).sum(axis=(1, 2)) > 0).all()  # every stage learns

    def test_head_params_and_input_grads_match_sequential(self):
        """The trainable loss head's grads (last stage) and the input
        cotangents (stage 0) must equal sequential autodiff — the paths
        the pipelined transformer's embedding training rides."""
        S, M, mb, D = 4, 5, 3, 8
        rng = np.random.RandomState(0)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        head = jnp.asarray(rng.randn(D, D), jnp.float32) * 0.2
        x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)
        y = jnp.asarray(rng.randn(M, mb, D), jnp.float32)

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        def loss_fn(act, yy, h):
            return jnp.mean((act @ h - yy) ** 2)

        def full_loss(ws_all, h, xx):
            total = 0.0
            for m in range(M):
                a = xx[m]
                for s in range(S):
                    a = jnp.tanh(a @ ws_all[s])
                total = total + loss_fn(a, y[m], h)
            return total / M

        egw, egh, egx = jax.grad(full_loss, argnums=(0, 1, 2))(ws, head, x)

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])

        def wrapped(w, h, xx, yy):
            loss, gw, gh, gx = one_f_one_b(
                stage_fn, w[0], xx, yy, loss_fn, axis_name="pp",
                head_params=h, return_input_grads=True)
            return (loss, gw[None], jax.lax.psum(gh, "pp"),
                    jax.lax.psum(gx, "pp"))

        loss, gw, gh, gx = jax.jit(jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=(P("pp", None, None), P(), P(), P()),
            out_specs=(P(), P("pp", None, None), P(), P()),
            check_vma=False))(ws, head, x, y)
        np.testing.assert_allclose(float(loss),
                                   float(full_loss(ws, head, x)), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(egw),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(egh),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(egx),
                                   rtol=1e-4, atol=1e-6)

    def test_training_loop_converges(self):
        """SGD on the 1F1B gradients reduces the loss (the grads are not
        just numerically right once; they drive optimization)."""
        S, M, mb, D = 4, 4, 4, 6
        rng = np.random.RandomState(7)
        ws = jnp.asarray(rng.randn(S, D, D), jnp.float32) * 0.3
        x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)
        y = jnp.asarray(rng.randn(M, mb, D), jnp.float32) * 0.1

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        def loss_fn(act, yy):
            return jnp.mean((act - yy) ** 2)

        mesh = create_hybrid_mesh(pp=S, devices=jax.devices()[:S])

        def train_step(w, xx, yy):
            loss, g = one_f_one_b(stage_fn, w[0], xx, yy, loss_fn,
                                  axis_name="pp")
            return loss, (w[0] - 0.5 * g)[None]

        f = jax.jit(jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(P("pp", None, None), P(), P()),
            out_specs=(P(), P("pp", None, None)), check_vma=False))
        losses = []
        for _ in range(30):
            loss, ws = f(ws, x, y)
            losses.append(float(loss))
        assert losses[-1] < 0.5 * losses[0], losses


class TestPPTransformer:
    """Pipelined transformer (dp x pp x tp over one_f_one_b): the sharded
    pipelined loss must equal a direct sequential implementation of the
    same architecture on the same parameter values, and training must
    reduce the loss."""

    CFG = dict(vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
               dtype=jnp.float32, unembed_dtype=jnp.float32,
               attn_backend="xla")

    def _reference_loss(self, params, tokens, labels, cfg):
        """Non-pipelined, non-sharded forward from the pp param layout."""
        from horovod_tpu.parallel.transformer import _rms_norm
        st = params["stages"]
        S, lps = st["wqkv"].shape[:2]
        d_head = cfg.d_model // cfg.n_heads
        x = params["embed"][tokens]
        for s in range(S):
            for i in range(lps):
                h = _rms_norm(x, st["ln1"][s, i])
                # head-major qkv layout (see pp_transformer._block)
                qkv = (h @ st["wqkv"][s, i]).reshape(
                    x.shape[0], x.shape[1], cfg.n_heads, 3, d_head)
                attn = _dense_attention(qkv[..., 0, :], qkv[..., 1, :],
                                        qkv[..., 2, :], causal=True)
                x = x + attn.reshape(x.shape[0], x.shape[1], -1) \
                    @ st["wo"][s, i]
                h = _rms_norm(x, st["ln2"][s, i])
                x = x + jax.nn.gelu(h @ st["w1"][s, i]) @ st["w2"][s, i]
        h = _rms_norm(x, params["lnf"])
        logits = h @ params["embed"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return float(jnp.mean(-jnp.take_along_axis(
            logp, labels[..., None], axis=-1)))

    @pytest.mark.parametrize("mesh_axes", [dict(dp=2, pp=2, tp=2),
                                           dict(dp=2, pp=4),
                                           dict(pp=2)])
    def test_loss_matches_sequential_reference(self, mesh_axes):
        cfg = TransformerConfig(**self.CFG)
        n_dev = int(np.prod(list(mesh_axes.values())))
        mesh = create_hybrid_mesh(devices=jax.devices()[:n_dev],
                                  **mesh_axes)
        init_state, step = make_pp_transformer_train_step(
            cfg, mesh, optax.sgd(0.0), n_microbatches=4)  # lr 0: loss probe
        params, opt_state = init_state(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (8, 8)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        _, _, loss = step(params, opt_state, tokens, labels)
        host_params = jax.tree_util.tree_map(np.asarray, params)
        host_params = jax.tree_util.tree_map(jnp.asarray, host_params)
        expect = self._reference_loss(host_params, tokens, labels, cfg)
        np.testing.assert_allclose(float(loss), expect, rtol=2e-5,
                                   atol=1e-6)

    def test_sgd_step_invariant_to_tp_size(self):
        """One SGD step from identical params must land on identical
        params at tp=2 and tp=1 — pins the BACKWARD pass across mesh
        shapes (an SGD probe catches any constant gradient mis-scaling
        that scale-invariant Adam hides; this exact bug shipped once:
        the tp psum-transpose doubled every tp-sharded weight's grad)."""
        cfg = TransformerConfig(**self.CFG)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (8, 8)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)

        results = {}
        for tp in (1, 2):
            kw = dict(pp=2)
            if tp > 1:
                kw["tp"] = tp
            mesh = create_hybrid_mesh(devices=jax.devices()[:2 * tp], **kw)
            init_state, step = make_pp_transformer_train_step(
                cfg, mesh, optax.sgd(0.1), n_microbatches=4)
            params, opt_state = init_state(jax.random.PRNGKey(0))
            params, _, loss = step(params, opt_state, tokens, labels)
            results[tp] = (float(loss),
                           jax.tree_util.tree_map(np.asarray, params))
        assert results[1][0] == pytest.approx(results[2][0], rel=1e-5)
        flat1 = jax.tree_util.tree_leaves(results[1][1])
        flat2 = jax.tree_util.tree_leaves(results[2][1])
        for a, b in zip(flat1, flat2):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_trains_dp_pp_tp(self):
        cfg = TransformerConfig(**self.CFG)
        mesh = create_hybrid_mesh(dp=2, pp=2, tp=2)
        init_state, step = make_pp_transformer_train_step(
            cfg, mesh, optax.adam(1e-2), n_microbatches=4)
        params, opt_state = init_state(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (16, 8)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        losses = []
        for _ in range(12):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           labels)
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < 0.7 * losses[0], losses


class TestParallelTransformer:
    def test_sgd_step_invariant_to_tp_size(self):
        """Same SGD-probe as the pipelined family: one step from identical
        params at tp=2 vs tp=1 must produce identical params (backward
        pass pinned across mesh shapes)."""
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, dtype=jnp.float32,
                                unembed_dtype=jnp.float32,
                                attn_backend="xla")
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (4, 16)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        results = {}
        for tp in (1, 2):
            mesh = create_hybrid_mesh(tp=tp, devices=jax.devices()[:tp])
            init_state, step = make_parallel_train_step(
                cfg, mesh, optax.sgd(0.1))
            params, opt_state = init_state(jax.random.PRNGKey(3))
            params, _, loss = step(params, opt_state, tokens, labels)
            results[tp] = (float(loss),
                           jax.tree_util.tree_map(np.asarray, params))
        assert results[1][0] == pytest.approx(results[2][0], rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(results[1][1]),
                        jax.tree_util.tree_leaves(results[2][1])):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_dp_tp_sp_train_step(self):
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, dtype=jnp.float32)
        mesh = create_hybrid_mesh(dp=2, sp=2, tp=2)
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.adam(1e-2))
        params, opt_state = init_state(jax.random.PRNGKey(0))

        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (4, 16)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses

    def test_sp_only_train_step(self):
        """Sequence-parallel-only mesh (no dp axis) must build a valid
        batch spec."""
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                                d_ff=64, dtype=jnp.float32)
        mesh = create_hybrid_mesh(sp=4, devices=jax.devices()[:4])
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.adam(1e-2))
        params, opt_state = init_state(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (2, 16)), jnp.int32)
        params, opt_state, loss = step(params, opt_state, tokens,
                                       jnp.roll(tokens, -1, axis=1))
        assert np.isfinite(float(loss))

    def test_n_experts_must_match_ep_axis(self):
        """The experts held must divide over the ep axis (several per
        rank are fine: 8 over ep=2 is 4 each; 7 is not)."""
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                                d_ff=64, n_experts=7, dtype=jnp.float32)
        mesh = create_hybrid_mesh(dp=4, ep=2)
        with pytest.raises(ValueError, match="n_experts"):
            make_parallel_train_step(cfg, mesh, optax.adam(1e-2))

    def test_dp_ep_moe_train_step(self):
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                                d_ff=64, n_experts=4, dtype=jnp.float32)
        mesh = create_hybrid_mesh(dp=2, ep=4)
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.adam(1e-2))
        params, opt_state = init_state(jax.random.PRNGKey(0))

        rng = np.random.RandomState(0)
        # Batch shards over dp×ep = 8.
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (8, 8)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses


class TestChunkedLoss:
    """loss_chunk: the online chunked cross-entropy must match the dense
    log_softmax path exactly — loss value AND one full train step's
    resulting params — while never materializing [*, vocab] logits."""

    CFG = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32, unembed_dtype=jnp.float32,
               attn_backend="xla")

    def _one_step(self, loss_chunk):
        from horovod_tpu.parallel.transformer import (
            TransformerConfig, make_parallel_train_step)
        from jax.sharding import Mesh
        cfg = TransformerConfig(**self.CFG, loss_chunk=loss_chunk)
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.sgd(0.1))
        params, opt_state = init_state(jax.random.PRNGKey(3))
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, 64, (4, 16)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        return float(loss), jax.tree_util.tree_map(np.asarray, params)

    def test_matches_dense_loss_and_step(self):
        dense_loss, dense_params = self._one_step(0)
        for chunk in (16, 32, 64):
            c_loss, c_params = self._one_step(chunk)
            np.testing.assert_allclose(c_loss, dense_loss, rtol=1e-5,
                                       atol=1e-6, err_msg=f"chunk={chunk}")
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=2e-5, atol=2e-6), c_params, dense_params)

    def test_chunk_must_divide_vocab(self):
        from horovod_tpu.parallel.transformer import (
            TransformerConfig, chunked_nll)
        cfg = TransformerConfig(**self.CFG, loss_chunk=48)
        with pytest.raises(ValueError, match="divide vocab"):
            chunked_nll(jnp.zeros((2, 4, 32)), jnp.zeros((64, 32)),
                        jnp.zeros((2, 4), jnp.int32), cfg)

    def test_out_of_range_labels_match_dense(self):
        """ADVICE r4 #1: a padding/ignore-index label (e.g. -1 or vocab)
        must produce the SAME per-token nll as the dense path (which clips
        via take_along_axis) — toggling loss_chunk must not change the
        loss on any input."""
        from horovod_tpu.parallel.transformer import (
            TransformerConfig, chunked_nll)
        cfg = TransformerConfig(**self.CFG, loss_chunk=16)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 4, 32), jnp.float32)
        embed = jnp.asarray(rng.randn(64, 32) * 0.1, jnp.float32)
        labels = jnp.asarray([[-1, 0, 63, 64], [7, -5, 100, 1]],
                             jnp.int32)

        logits = x @ embed.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        dense = -jnp.take_along_axis(
            logp, jnp.clip(labels, 0, 63)[..., None], axis=-1)[..., 0]
        got = chunked_nll(x, embed, labels, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)


class TestPackedQKVAttention:
    """The packed-qkv kernel branch (d_head=128, pallas backend) must
    compute the same function as the xla-backend split path INSIDE the
    sharded train step — including under tensor parallelism, where heads
    shard and n_heads_local differs from n_heads."""

    def _two_steps(self, backend, mesh_axes):
        from horovod_tpu.parallel.transformer import (
            TransformerConfig, make_parallel_train_step)
        from horovod_tpu.parallel.mesh import create_hybrid_mesh
        cfg = TransformerConfig(vocab=64, d_model=256, n_heads=2,
                                n_layers=2, d_ff=128, dtype=jnp.float32,
                                unembed_dtype=jnp.float32,
                                attn_backend=backend)  # d_head = 128
        n_dev = int(np.prod(list(mesh_axes.values())))
        mesh = create_hybrid_mesh(devices=jax.devices()[:n_dev],
                                  **mesh_axes)
        init_state, step = make_parallel_train_step(cfg, mesh,
                                                    optax.sgd(0.1))
        params, opt = init_state(jax.random.PRNGKey(7))
        rng = np.random.RandomState(3)
        tokens = jnp.asarray(rng.randint(0, 64, (4, 256)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        params, opt, l1 = step(params, opt, tokens, labels)
        _, _, l2 = step(params, opt, tokens, labels)
        return float(l1), float(l2)

    @pytest.mark.parametrize("mesh_axes", [dict(dp=2), dict(dp=2, tp=2)])
    def test_matches_xla_backend(self, mesh_axes):
        xla = self._two_steps("xla", mesh_axes)
        packed = self._two_steps("pallas", mesh_axes)
        np.testing.assert_allclose(packed, xla, rtol=1e-4, atol=1e-5)
