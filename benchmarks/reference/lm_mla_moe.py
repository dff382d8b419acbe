"""Plain reference of the decoder the ``lm_mla_moe`` family trains (the
DeepSeek-V3 block: ``model_type`` ``deepseek_v3``): multi-head latent
attention on every layer, its decoupled key part ROTATED by position, a
leading dense feed-forward and, after it, top-k mixtures of gated SiLU
experts under sigmoid scores of which only the ``held`` ones are computed,
plus shared experts without a gate; untied head. Forward, loss and
(through ``jax.grad``) gradients in straightforward ``jax.numpy``, float32,
matmuls at ``highest`` precision, no kernel, no sharding; written from the
layers' equations (ISSUE 38, item 1) and NOT from ``horovod_tpu/``. The
plain norm, expert and blocked-attention helpers are those of
``reference/lm_kda_mla_moe.py``.

For x [T, D] and positions t = 0..T-1 in each sequence, each layer:

    x += MLA(rms(x; ln1));   x += FF(rms(x; ln2))
    rms(x; w) = x / sqrt(mean(x^2) + eps) * w     (a plain weight)

MLA (H heads; latent rank r; key parts dn + dr; value dv):

    q = h Wq                                 [T, H, dn + dr]
    [c | kr] = h Wkva                        [T, r + dr]
    [kn | v]_head = rms(c; wc) Wkvb          [T, H, dn + dv]
    qr = RoPE(q[..., dn:], t);  kr = RoPE(kr, t)   (kr: ONE vector a token,
                                                   shared by all heads)
    P = causal softmax([qn | qr] . [kn | kr] / sqrt(dn + dr))
    out = concat_heads(P v) Wo

RoPE over d = dr columns of base theta, inverse frequencies
theta^(-2i/d) for i < d/2, rotates the pairs (2i, 2i + 1) of the published
columns (``rope_interleave``), no scaling:

    (x_2i, x_2i+1) -> (x_2i cos - x_2i+1 sin, x_2i+1 cos + x_2i sin),
    angle t theta^(-2i/d)

FF of the leading dense layers: (silu(h Wg) * h Wu) Wd. FF of every other
layer: s = sigmoid(h Wr) in float32 over all E experts; E_t = top-k of
s + b (b: ``router_bias``, for the selection alone); w_e = scaling * s_e /
sum_{E_t} s; FF = sum over e in E_t that are held of w_e SwiGLU_e(h), plus
SwiGLU_shared(h) (the published ``n_shared_experts`` as one MLP of their
summed width, no gate). Where experts are absent, so is the routing
weights' gradient (it needs their outputs). Then the final rms, the untied
head over the vocabulary rows held, and the mean token NLL.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.lm_kda_mla_moe import _experts, _gated, _rms, attention


def rope(x, theta: float):
    """x [B, T, ..., d], rotated by position t along axis 1: the pairs
    (2i, 2i + 1) of the last axis by the angle t theta^(-2i/d)."""
    T, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, T) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(h, layer, hp, q_block):
    """h [B, T, D] -> (the mixer's output [B, T, D], the attention's output
    [B, T, H, dv]); the scores of ``q_block`` query rows at a time."""
    B, T, _ = h.shape
    H, r = hp["n_heads"], hp["kv_rank"]
    dn, dr, dv = hp["d_nope"], hp["d_rope"], hp["d_v"]
    q = (h @ layer["mla_wq"]).reshape(B, T, H, dn + dr)
    latent = h @ layer["mla_wkva"]
    kv = (_rms(latent[..., :r], layer["mla_kv_norm"], hp["eps"])
          @ layer["mla_wkvb"]).reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], hp["rope_theta"])],
                        axis=-1)
    kr = rope(latent[:, :, None, r:], hp["rope_theta"])
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (B, T, H, dr))],
                        axis=-1)
    o = attention(q, k, kv[..., dn:], q_block)
    return o.reshape(B, T, H * dv) @ layer["mla_wo"], o


def forward(params, tokens, labels, hp, routing=None, q_block=512):
    """``params`` in the published layout (the rotated columns as the
    checkpoint has them). ``hp``: n_heads, kv_rank, d_nope, d_rope, d_v,
    rope_theta, experts_per_tok, first_expert, scaling, eps. ``routing``:
    None (the reference routes) or per EXPERT layer the [B*T, k] expert ids
    to compute. Returns {"logits", "nll" [B, T], "loss", "mla_o": the
    attention's output of each layer, "routed": the reference's own ids of
    each expert layer}. T must be a multiple of ``q_block`` where it is
    longer."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens]
        B, T, D = x.shape
        mla_o, routed = [], []
        for layer in params["layers"]:
            out, o = latent_attention(_rms(x, layer["ln1"], hp["eps"]),
                                      layer, hp, q_block)
            mla_o.append(o)
            x = x + out
            h2 = _rms(x, layer["ln2"], hp["eps"]).reshape(B * T, D)
            if "router" in layer:
                y, own = _experts(h2, layer, hp, None if routing is None
                                  else routing[len(routed)])
                routed.append(own)
            else:
                y = _gated(h2, layer["w_gate"], layer["w_up"],
                           layer["w_down"])
            x = x + y.reshape(B, T, D)
        logits = _rms(x, params["lnf"], hp["eps"]) @ params["head"].T
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return {"logits": logits, "nll": nll, "loss": jnp.mean(nll),
                "mla_o": mla_o, "routed": routed}
