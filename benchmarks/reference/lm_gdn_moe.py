"""Plain reference of the decoder the ``lm_gdn_moe`` family trains: layers of
two kinds in a fixed period, Gated DeltaNet linear attention and gated
softmax attention, each followed by a top-k mixture of gated SiLU experts
of which only the ``held`` ones are computed, plus one shared expert behind
a sigmoid gate; untied head. Forward, loss and (through ``jax.grad``)
gradients in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernel, no chunking of the recurrence, no sharding; written
from the layers' equations (ISSUE 32, section 1; the published form is
``modeling_qwen3_next`` in ``transformers``) and NOT from ``horovod_tpu/``.

All norms: ``rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)``, except the
DeltaNet output norm, whose weight is plain. Layer i (from 0) is a
full-attention layer iff (i + 1) % full_interval == 0. For x [T, D]:

    x += mixer(rms(x; ln1));   x += experts(rms(x; ln2))

Gated DeltaNet mixer (Hk key heads, Hv value heads, dk, dv; h [T, D]):

    [q | k | v | z] = h Wqkvz;  [b | a] = h Wba
    [q | k | v] <- silu(conv(.)),  conv_t = sum_j c[j] x_{t-3+j} per channel
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)      [T, Hv]
    q, k: each key head serves Hv/Hk consecutive value heads;
          x / sqrt(sum(x^2) + 1e-6) over dk; q scaled by dk^-1/2
    per head, S [dk, dv] from zero, row by row:
        S <- exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T
        o_t = S^T q_t
    out = (o_t / sqrt(mean(o_t^2) + 1e-6) * w_n * silu(z_t)) Wout

Gated attention mixer (H heads, Hkv key/value heads, dh; rotary_dim r):

    [q_n | gate_n] = (h Wq) per head;  k, v = h Wk, h Wv
    q, k <- rope(rms_head(.)): pairs (i, i + r/2) of the first r of a head
            rotated by position t with base theta; the rest untouched
    P = causal softmax(q k^T / sqrt(dh)), key head n // (H / Hkv)
    out = concat_n(P v * sigmoid(gate_n)) Wo

Experts: r = softmax(h Wr); E_t = top-k of r; g_e = r_e / sum_{E_t} r;
the sum over e in E_t that are held of g_e (silu(h Wg_e) * h Wu_e) Wd_e,
plus sigmoid(h w_s) (silu(h Wg_s) * h Wu_s) Wd_s. Where experts are
absent, so is the routing weights' gradient (it needs their outputs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6


def _rms(x, w):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + EPS)


def _rope(x, theta, r):
    """x [B, T, H, d]: the first r of the last dim rotated in pairs
    (i, i + r/2) by position t; the rest as it is."""
    T = x.shape[1]
    inv = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k [B, T, H, dk], v [B, T, H, dv],
    g, beta [B, T, H] -> o [B, T, H, dv]."""
    B, T, H, dk = q.shape

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[..., None, None]
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * b_t[..., None]
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32),
                    rows)
    return jnp.moveaxis(o, 0, 1)


def _delta_net(h, layer, hp):
    """h [B, T, D] -> (the mixer's output [B, T, D], the rule's o)."""
    B, T, _ = h.shape
    Hk, Hv, dk, dv = hp["gdn_k_heads"], hp["gdn_v_heads"], hp["gdn_dk"], \
        hp["gdn_dv"]
    nk, nv = Hk * dk, Hv * dv
    qkvz = h @ layer["gdn_wqkvz"]
    ba = h @ layer["gdn_wba"]
    conv = layer["gdn_conv"]
    width = conv.shape[0]
    x = jnp.pad(qkvz[..., :2 * nk + nv], ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(x[:, j:j + T] * conv[j] for j in range(width)))
    q = qkv[..., :nk].reshape(B, T, Hk, dk)
    k = qkv[..., nk:2 * nk].reshape(B, T, Hk, dk)
    v = qkv[..., 2 * nk:].reshape(B, T, Hv, dv)
    z = qkvz[..., 2 * nk + nv:].reshape(B, T, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(layer["gdn_a_log"]) * jax.nn.softplus(
        ba[..., Hv:] + layer["gdn_dt_bias"])
    q = jnp.repeat(_unit(q) * dk ** -0.5, Hv // Hk, axis=2)
    k = jnp.repeat(_unit(k), Hv // Hk, axis=2)
    o = delta_rule(q, k, v, g, beta)
    out = _rms(o, layer["gdn_norm"]) * jax.nn.silu(z)
    return out.reshape(B, T, nv) @ layer["gdn_wout"], o


def _attention(h, layer, hp, q_block):
    """h [B, T, D] -> the gated attention mixer's output [B, T, D]; the
    scores of ``q_block`` query rows at a time."""
    B, T, _ = h.shape
    H, Hkv, dh = hp["n_heads"], hp["n_kv_heads"], hp["d_head"]
    qg = (h @ layer["wq"]).reshape(B, T, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (h @ layer["wk"]).reshape(B, T, Hkv, dh)
    v = (h @ layer["wv"]).reshape(B, T, Hkv, dh)
    q = _rope(_rms(q, 1.0 + layer["q_norm"]), hp["rope_theta"],
              hp["rotary_dim"])
    k = _rope(_rms(k, 1.0 + layer["k_norm"]), hp["rope_theta"],
              hp["rotary_dim"])
    kk = jnp.repeat(k, H // Hkv, axis=2)
    vv = jnp.repeat(v, H // Hkv, axis=2)
    R = min(q_block, T)

    def block(i):
        b, r0 = i // (T // R), (i % (T // R)) * R
        rows = lax.dynamic_slice_in_dim(q[b], r0, R, axis=0)
        s = jnp.einsum("rnd,snd->nrs", rows, kk[b]) * dh ** -0.5
        seen = jnp.arange(T)[None, :] <= (r0 + jnp.arange(R))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nrs,snd->rnd", p, vv[b])
    out = lax.map(block, jnp.arange(B * (T // R))).reshape(B, T, H, dh)
    out = out * jax.nn.sigmoid(gate)
    return out.reshape(B, T, H * dh) @ layer["wo"]


def _experts(h2, layer, hp, given):
    """h2 [N, D] -> (the held experts' share plus the shared expert, the
    reference's own top-k ids [N, k]). ``given`` ([N, k] ids or None):
    the sets whose experts are computed, weighted by the reference's own
    probabilities renormalised over them."""
    r = jax.nn.softmax(h2 @ layer["router"], axis=-1)
    own = lax.top_k(r, hp["experts_per_tok"])[1]
    ids = own if given is None else given
    top = jnp.take_along_axis(r, ids, axis=-1)
    gate = top / jnp.sum(top, axis=-1, keepdims=True)
    if layer["w_up"].shape[0] < r.shape[-1]:
        gate = lax.stop_gradient(gate)
    y = jnp.zeros_like(h2)
    for j in range(layer["w_up"].shape[0]):
        g = jnp.sum(jnp.where(ids == hp["first_expert"] + j, gate, 0.0), -1)
        act = jax.nn.silu(h2 @ layer["w_gate"][j]) * (h2 @ layer["w_up"][j])
        y = y + g[:, None] * (act @ layer["w_down"][j])
    act = jax.nn.silu(h2 @ layer["shared_gate"]) * (h2 @ layer["shared_up"])
    shared = (act @ layer["shared_down"]) * jax.nn.sigmoid(
        h2 @ layer["shared_w"])
    return y + shared, own


def forward(params, tokens, labels, hp, routing=None, q_block=512):
    """``hp``: n_heads, n_kv_heads, d_head, rotary_dim, rope_theta,
    full_interval, gdn_k_heads, gdn_v_heads, gdn_dk, gdn_dv,
    experts_per_tok, first_expert. ``routing``: None (the reference routes)
    or per layer the [B*T, k] expert ids to compute. Returns {"logits",
    "nll" [B, T], "loss", "gdn_o": the rule's o of each DeltaNet layer,
    "routed": the reference's own ids of each layer}. T must be a multiple
    of ``q_block`` where it is longer."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens]
        B, T, D = x.shape
        gdn_o, routed = [], []
        for li, layer in enumerate(params["layers"]):
            h = _rms(x, 1.0 + layer["ln1"])
            if (li + 1) % hp["full_interval"] == 0:
                x = x + _attention(h, layer, hp, q_block)
            else:
                out, o = _delta_net(h, layer, hp)
                x = x + out
                gdn_o.append(o)
            h2 = _rms(x, 1.0 + layer["ln2"]).reshape(B * T, D)
            y, own = _experts(h2, layer, hp,
                              None if routing is None else routing[li])
            x = x + y.reshape(B, T, D)
            routed.append(own)
        logits = _rms(x, 1.0 + params["lnf"]) @ params["head"].T
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return {"logits": logits, "nll": nll, "loss": jnp.mean(nll),
                "gdn_o": gdn_o, "routed": routed}
