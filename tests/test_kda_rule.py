"""The delta rule with a decay per channel (Kimi Delta Attention) and
attention with a key width of its own, at a small size on the CPU: the
chunked rule (both backends; the Pallas kernels in interpret mode) against
its token-by-token recurrence ``benchmarks/reference/lm_kda_mla_moe.py``,
forward and every gradient, with channels that forget fast; a gate constant
over a head's channels against the scalar rule; the kernels against the
``jax.numpy`` stage; ``flash_attention`` at 192 / 128 against the XLA
attention, and the error where it may not fall back."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import lm_kda_mla_moe as reference  # noqa: E402

from horovod_tpu.obs.registry import parse_exposition, registry  # noqa: E402
from horovod_tpu.ops import gated_delta as gd  # noqa: E402
from horovod_tpu.ops import pallas_attention as pa  # noqa: E402

F32 = jnp.float32


# -- the rule -----------------------------------------------------------------


def rule_inputs(T, seed=0, B=2, Hk=2, Hv=2, dk=16, dv=32, fast=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, Hk, dk)))
    v = jax.random.normal(ks[2], (B, T, Hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, Hv, dk))) * 0.3
    if fast:
        # A third of the channels forget within a row (g about -30 a row,
        # e^-1900 over a chunk of 64: nothing may overflow).
        g = jnp.where(jax.random.uniform(ks[5], (1, 1, Hv, dk)) < 0.3,
                      g * 100.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv)))
    return tuple(a.astype(F32) for a in (q, k, v, g, beta))


def recurrence(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return reference.delta_rule(jnp.repeat(q, rep, axis=2),
                                jnp.repeat(k, rep, axis=2), v, g, beta)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("chunk,T,fast,heads", [
    (64, 256, True, (2, 2)), (16, 40, True, (2, 2))],
    ids=["c64_four_chunks_fast", "c16_padded_fast"])
def test_per_channel_rule_matches_the_recurrence(backend, chunk, T, fast,
                                                 heads):
    """Forward and the gradients of q, k, v, g [B, T, H, dk] and beta."""
    args = rule_inputs(T, fast=fast, Hk=heads[0], Hv=heads[1])
    if fast:
        assert float(jnp.min(args[3])) < -30.0
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, F32)

    def chunked(*a):
        return gd.gated_delta_rule(*a, chunk=chunk, backend=backend)
    got, want = chunked(*args), recurrence(*args)
    assert got.shape == want.shape == args[2].shape
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-6)
    g_got = jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
    assert g_got[3].shape == args[3].shape
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(
            jnp.abs(b))) + 1e-6, err_msg=name)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_gate_constant_over_the_channels_is_the_scalar_rule(backend):
    """The two paths, tied: g [B, T, H] against the same g laid over the
    dk channels of its head; the channels' gradient sums to the head's."""
    q, k, v, g, beta = rule_inputs(96, Hk=2, Hv=4)
    g = g[..., 0]
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    weight = jax.random.normal(jax.random.PRNGKey(3), v.shape, F32)

    def loss(g):
        # A gate per channel takes a key head a value head.
        rep = 2 if g.ndim == 4 else 1
        o = gd.gated_delta_rule(jnp.repeat(q, rep, axis=2),
                                jnp.repeat(k, rep, axis=2), v, g, beta,
                                chunk=16, backend=backend)
        return jnp.sum(o * weight), o
    (_, scalar), d_scalar = jax.value_and_grad(loss, has_aux=True)(g)
    (_, channel), d_channel = jax.value_and_grad(loss, has_aux=True)(wide)
    np.testing.assert_allclose(channel, scalar, atol=2e-6)
    np.testing.assert_allclose(d_channel.sum(-1), d_scalar, atol=2e-5)


def test_per_channel_kernels_match_the_jax_numpy_stage():
    """``kda_local_fwd`` / ``kda_local_bwd`` (interpreted) against the same
    stage mapped over the chunks by XLA, tiles of two chunks, bf16 and
    float32 operands, every output and every cotangent."""
    from horovod_tpu.ops import pallas_gated_delta as pgd
    for dtype, tol in ((F32, 1e-5), (jnp.bfloat16, 2e-2)):
        q, k, v, g, beta = rule_inputs(256, B=1, dk=16, dv=32, fast=True)

        def chunks(x):
            x = jnp.moveaxis(x, 2, 1)
            return x.reshape(1, 2, 4, 64, *x.shape[3:])
        q, k, v = (chunks(x).astype(dtype) for x in (q, k, v))
        g, beta = chunks(g), chunks(beta)
        want = gd._channel_fwd_xla(q, k, v, g, beta)
        got = pgd.kda_local_fwd(q, k, v, g, beta)
        assert got[6].shape == (1, 2, 2, 64, 128)       # T packed
        for name, a, b in zip("qg kd w u aqk e".split(), got, want):
            np.testing.assert_allclose(
                a.astype(F32), b.astype(F32), err_msg=name,
                atol=tol * (1 + float(jnp.max(jnp.abs(b.astype(F32))))))
        cot = [jax.random.normal(jax.random.PRNGKey(i), x.shape,
                                 F32).astype(x.dtype)
               for i, x in enumerate(want[:6])]
        g_want = gd._channel_bwd_xla(q, k, v, g, beta, want[6], *cot)
        g_got = pgd.kda_local_bwd(q, k, v, g, beta, got[6], *cot)
        for name, a, b in zip("q k v g beta".split(), g_got, g_want):
            np.testing.assert_allclose(
                a.astype(F32), b.astype(F32), err_msg=name,
                atol=tol * (1 + float(jnp.max(jnp.abs(b.astype(F32))))))


def test_rule_refuses_a_gate_it_cannot_chunk():
    q, k, v, g, beta = rule_inputs(48)
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=24, backend="xla")
    with pytest.raises(ValueError, match="per channel"):
        gd.gated_delta_rule(q, k, v, g[..., :8], beta, chunk=16,
                            backend="xla")
    # Value heads that share a key head have decays of their own.
    wide = [jnp.repeat(x, 2, axis=2) for x in (v, g, beta)]
    with pytest.raises(ValueError, match="a key head a value head"):
        gd.gated_delta_rule(q, k, *wide, chunk=16, backend="xla")


def test_saved_bytes_and_gauges_count_the_wider_gate():
    # q, k, v [2, 40 -> 48, 2, 16 | 32] float32 and a [16, 32] state a
    # chunk of 16 and head; g 16, beta 1 and a row of the solve 16 float32
    # a row and head.
    want = 96 * 2 * (32 + 32) * 4 + 96 * 2 * (16 + 1 + 16) * 4 \
        + 6 * 2 * 16 * 32 * 4
    assert gd.saved_bytes((2, 40, 2, 16), 2, 32, 4, 16,
                          per_channel=True) == want
    gd.record_saved(9, (2, 40, 2, 16), 2, 32, 4, 16, per_channel=True)
    samples = parse_exposition(registry().render())
    assert samples[("hvd_kda_saved_state_bytes", (("layer", "9"),))] == want
    q, k, v, g, beta = rule_inputs(64)
    for backend, local in (("xla", 0), ("pallas", 1)):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=16, backend=backend,
                            layer=9)
        samples = parse_exposition(registry().render())
        assert samples[("hvd_gdn_local_kernel", (("layer", "9"),))] == local


# -- attention with a q/k width of its own ------------------------------------


@pytest.mark.parametrize("dqk,dv", [(192, 128), (24, 128), (256, 128)])
def test_flash_attention_takes_a_key_width_of_its_own(dqk, dv):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (1, 256, 2, dqk), F32) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 256, 2, dv), F32)
    w = jax.random.normal(ks[3], (1, 256, 2, dv), F32)

    def loss(backend):
        return lambda q, k, v: jnp.sum(pa.flash_attention(
            q, k, v, causal=True, backend=backend, fallback=False) * w)
    got = pa.flash_attention(q, k, v, causal=True, backend="pallas",
                             fallback=False)
    want = pa._xla_attention(q, k, v, True, dqk ** -0.5)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for a, b in zip(jax.grad(loss("pallas"), (0, 1, 2))(q, k, v),
                    jax.grad(loss("xla"), (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_flash_attention_refuses_where_it_may_not_fall_back():
    x = jnp.zeros((1, 100, 2, 192), F32)
    v = jnp.zeros((1, 100, 2, 128), F32)
    with pytest.raises(ValueError, match="no fallback"):
        pa.flash_attention(x, x, v, causal=True, backend="pallas",
                           fallback=False)
    # The packed block's callers keep theirs.
    assert pa.flash_attention(x, x, v, causal=True,
                              backend="pallas").shape == v.shape
    # ... and so does a value width the kernels cannot tile.
    with pytest.raises(ValueError, match="no fallback"):
        pa.flash_attention(jnp.zeros((1, 128, 2, 24)), jnp.zeros(
            (1, 128, 2, 24)), jnp.zeros((1, 128, 2, 16)), backend="pallas",
            fallback=False)
