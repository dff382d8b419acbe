"""Plain reference of the decoder the ``lm_swa_moe`` family trains (the
afmoe block, ``model_type`` ``afmoe``: Arcee's Trinity): grouped-query
attention of two kinds, over a sliding window with positions and over the
whole causal past without them, each behind a sigmoid output gate, sandwich
norms, a leading dense feed-forward and, after it, top-k mixtures of gated
SiLU experts under sigmoid scores of which only the ``held`` ones are
computed, plus one shared expert without a gate; untied head. Forward, loss
and (through ``jax.grad``) gradients in straightforward ``jax.numpy``,
float32, matmuls at ``highest`` precision, no kernel, no sharding; written
from the layers' equations and NOT from ``horovod_tpu/``, on
the published weight layout: the gate's projection is a weight of its own
(``w_attn_gate``). The plain norm and expert helpers are those of
``reference/lm_kda_mla_moe.py``.

All norms: ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, plain weight.
For tokens t of a sequence, x_0 = E[t] * embed_scale, and each layer:

    x += rms(mixer(rms(x; ln1)); post_ln1)
    x += rms(ff(rms(x; ln2)); post_ln2)

The mixer (H query heads and G key/value heads of dh; query head n reads
key/value head n // (H / G)):

    q = h Wq, k = h Wk, v = h Wv;  q, k = rms(q; wq_n), rms(k; wk_n) a head
    window layers: q, k = RoPE(q, t), RoPE(k, t)   (full layers: none)
    P = softmax over the keys j that row i sees of q_i . k_j / sqrt(dh)
        (window layers: i - W < j <= i, W keys; full layers: j <= i)
    out = (concat_heads(P v) * sigmoid(h W_gate)) Wo

RoPE of base theta over dh columns rotates the pairs (i, i + dh/2) by the
angle t theta^(-2i/dh). The feed-forward of the leading dense layers is
(silu(h Wg) * h Wu) Wd; of every other layer, s = sigmoid(h Wr) in float32
over all E experts; E_t = top-k of s + b (b: ``router_bias``, for the
selection alone); w_e = scaling * s_e / sum_{E_t} s; FF = the sum over e in
E_t that are held of w_e SwiGLU_e(h), plus SwiGLU_shared(h). Where experts
are absent, so is the routing weights' gradient (it needs their outputs).
Then the final rms, the untied head over the vocabulary rows held, and the
mean token NLL.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference.lm_kda_mla_moe import _experts, _gated, _rms


def rope(x, theta: float):
    """x [B, T, H, d], rotated by position t along axis 1: the pairs
    (i, i + d/2) by the angle t theta^(-2i/d)."""
    T, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window=None, q_block=512):
    """Causal softmax attention of q [B, T, H, d] over k [B, T, G, d] and
    v [B, T, G, dv] (G divides H), scaled by d^-1/2 -> [B, T, H, dv].
    ``window``: row i sees the keys i - window < j <= i alone. The scores of
    ``q_block`` query rows at a time (T a multiple of it where it is
    longer), against the keys of their band alone; a block's scores are
    made again in a backward, not kept."""
    B, T, H, d = q.shape
    G, dv = k.shape[2], v.shape[-1]
    R = min(q_block, T)
    L = T if window is None else min(T, R + window - 1)

    @jax.checkpoint
    def block(i):
        b, r0 = i // (T // R), (i % (T // R)) * R
        s0 = jnp.clip(r0 + R - L, 0, T - L)
        rows = lax.dynamic_slice_in_dim(q[b], r0, R).reshape(R, G, H // G, d)
        keys = lax.dynamic_slice_in_dim(k[b], s0, L)
        values = lax.dynamic_slice_in_dim(v[b], s0, L)
        s = jnp.einsum("rgnd,sgd->gnrs", rows, keys) * d ** -0.5
        gap = (r0 + jnp.arange(R))[:, None] - (s0 + jnp.arange(L))[None, :]
        seen = gap >= 0
        if window is not None:
            seen = seen & (gap < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gnrs,sgd->rgnd", p, values).reshape(R, H, dv)
    return lax.map(block, jnp.arange(B * (T // R))).reshape(B, T, H, dv)


def gated_attention(h, layer, hp, windowed: bool, q_block):
    """h [B, T, D] -> (the mixer's output [B, T, D], the attention's output
    [B, T, H, dh], before the gate)."""
    B, T, _ = h.shape
    H, G, dh = hp["n_heads"], hp["n_kv_heads"], hp["d_head"]
    q = _rms((h @ layer["wq"]).reshape(B, T, H, dh), layer["q_norm"],
             hp["eps"])
    k = _rms((h @ layer["wk"]).reshape(B, T, G, dh), layer["k_norm"],
             hp["eps"])
    v = (h @ layer["wv"]).reshape(B, T, G, dh)
    if windowed:
        q, k = rope(q, hp["rope_theta"]), rope(k, hp["rope_theta"])
    o = attention(q, k, v, hp["window"] if windowed else None, q_block)
    gate = jax.nn.sigmoid(h @ layer["w_attn_gate"])
    return (o.reshape(B, T, H * dh) * gate) @ layer["wo"], o


def forward(params, tokens, labels, hp, routing=None, q_block=512):
    """``params`` in the published layout. ``hp``: n_heads, n_kv_heads,
    d_head, window, rope_theta, kinds (per layer "window" or "full"),
    embed_scale, experts_per_tok, first_expert, scaling, eps. ``routing``:
    None (the reference routes) or per EXPERT layer the [B*T, k] expert ids
    to compute. Returns {"logits", "nll" [B, T], "loss", "attn_o": the
    attention's output of each layer, "routed": the reference's own ids of
    each expert layer}."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens] * hp["embed_scale"]
        B, T, D = x.shape
        attn_o, routed = [], []
        for layer, kind in zip(params["layers"], hp["kinds"]):
            out, o = gated_attention(_rms(x, layer["ln1"], hp["eps"]), layer,
                                     hp, kind == "window", q_block)
            attn_o.append(o)
            x = x + _rms(out, layer["post_ln1"], hp["eps"])
            h2 = _rms(x, layer["ln2"], hp["eps"]).reshape(B * T, D)
            if "router" in layer:
                y, own = _experts(h2, layer, hp, None if routing is None
                                  else routing[len(routed)])
                routed.append(own)
            else:
                y = _gated(h2, layer["w_gate"], layer["w_up"],
                           layer["w_down"])
            x = x + _rms(y.reshape(B, T, D), layer["post_ln2"], hp["eps"])
        logits = _rms(x, params["lnf"], hp["eps"]) @ params["head"].T
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return {"logits": logits, "nll": nll, "loss": jnp.mean(nll),
                "attn_o": attn_o, "routed": routed}
