"""Plain reference of the decoder the ``lm_moe_dsa`` family trains: a
grouped-query attention block whose keys are chosen per query row by a
lightning indexer (DeepSeek-V3.2's sparse attention: top-k of a learned
score, shared by all heads), followed by a top-k mixture of gated SiLU
experts of which only the ``held`` ones are computed; untied head. Forward,
loss and (through ``jax.grad``) gradients in straightforward ``jax.numpy``,
float32, matmuls at ``highest`` precision, no kernel, no sharding, written
from the layer's equations (ISSUE 28, "The layer") and NOT from
``horovod_tpu/``.

Per layer, for x [T, D] (all batched over B):

    h   = RMSNorm(x; ln1)
    q   = RoPE(RMSNorm_head(h Wq; q_norm))          [T, Hq, dh]
    k   = RoPE(RMSNorm_head(h Wk; k_norm)), v = h Wv [T, Hkv, dh]
    qI  = RoPE(sg(h) WIq) [T, Hi, di];  kI = RoPE(LayerNorm(sg(h) WIk)) [T, di]
    w   = sg(h) WIw / sqrt(Hi di)                    [T, Hi]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
    S_t = the min(topk, t+1) positions s <= t of largest I[t, s] (ties: smaller s)
    P[n, t, .] = softmax over S_t of q[t, n] . k[., n // (Hq/Hkv)] / sqrt(dh)
    x  += concat_n(P[n] v) Wo
    KL_t = KL( sg(mean_n P[n, t, .]) || softmax over S_t of I[t, .] )
    h2  = RMSNorm(x; ln2); r = softmax(h2 Wr); E_t = top-k experts of r
    g_e = r_e / sum_{e' in E_t} r_e'
    x  += sum_{e in E_t, e held} g_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

loss = mean token NLL over the head's rows + mean over layers and rows of
KL_t. RoPE rotates the pairs (i, i + d/2) of a head by position t with
base ``theta``. ``sg`` is stop-gradient. Nothing is dropped; what experts
outside ``held`` would add is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _rope(x, theta):
    """x [B, T, ..., d]: rotate pairs (i, i + d/2) by position t."""
    d = x.shape[-1]
    T = x.shape[1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, T) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def select(scores, topk):
    """scores [R, T] with -inf where s > t -> bool [R, T]: the
    min(topk, t+1) largest of each row, equal scores to the smaller s
    (``lax.top_k`` puts the lower index first among equals)."""
    R, T = scores.shape
    vals, idx = lax.top_k(scores, min(topk, T))
    rows = jnp.arange(R)[:, None]
    return jnp.zeros((R, T), bool).at[rows, idx].set(vals > -jnp.inf)


def _attend_block(q, k, v, qi, ki, w, rows0, topk, given):
    """One sequence, query rows [rows0, rows0+R): returns the attention
    output [R, Hq, dh], the KL of each row [R] and the reference's own
    selection [R, T]. Attention and KL are over ``given`` where a selection
    is handed in, else over the reference's own."""
    R, Hq, dh = q.shape
    T, Hkv, _ = k.shape
    t = rows0 + jnp.arange(R)[:, None]
    causal = jnp.arange(T)[None, :] <= t
    index = jnp.einsum("rj,rjs->rs", w, jax.nn.relu(
        jnp.einsum("rjd,sd->rjs", qi, ki)))
    index = jnp.where(causal, index, -jnp.inf)
    own = select(index, topk)
    sel = own if given is None else given
    kk = jnp.repeat(k, Hq // Hkv, axis=1)
    vv = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("rnd,snd->nrs", q, kk) * dh ** -0.5
    p = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("nrs,snd->rnd", p, vv)
    p_mean = lax.stop_gradient(jnp.mean(p, axis=0))
    log_pi = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(p_mean > 0, p_mean * (
        jnp.log(jnp.where(p_mean > 0, p_mean, 1.0))
        - jnp.where(sel, log_pi, 0.0)), 0.0), axis=-1)
    return out, kl, own


def _experts(h2, layer, hp):
    """h2 [N, D] -> the held experts' share of the layer's output."""
    r = jax.nn.softmax(h2 @ layer["router"], axis=-1)
    top, ids = lax.top_k(r, hp["experts_per_tok"])
    gate = top / jnp.sum(top, axis=-1, keepdims=True)
    if layer["w_up"].shape[0] < r.shape[-1]:
        # Experts are absent: the weights' gradient needs their outputs
        # too, so it is left out with them (no gradient to the router).
        gate = lax.stop_gradient(gate)
    y = jnp.zeros_like(h2)
    for j in range(layer["w_up"].shape[0]):
        g = jnp.sum(jnp.where(ids == hp["first_expert"] + j, gate, 0.0), -1)
        act = jax.nn.silu(h2 @ layer["w_gate"][j]) * (h2 @ layer["w_up"][j])
        y = y + g[:, None] * (act @ layer["w_down"][j])
    return y


def forward(params, tokens, labels, hp, selections=None, q_block=512):
    """``hp``: n_heads, n_kv_heads, d_head, idx_heads, idx_dim, topk,
    experts_per_tok, first_expert, rope_theta. ``selections``: None (the
    reference selects), or per layer a bool [B, T, T] to attend over.
    Returns {"nll": [B, T], "kl": [L, B, T], "selected": [L][B, T, T] (the
    reference's OWN selection from its own index scores, whatever it
    attended over), "loss": scalar}. Query rows are taken ``q_block`` at a time (T must be a
    multiple of it), so that T 8192 fits a chip: [32, 512, 8192] float32
    scores are 0.54 GB."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens]
        B, T, D = x.shape
        Hq, Hkv, dh = hp["n_heads"], hp["n_kv_heads"], hp["d_head"]
        Hi, di, theta = hp["idx_heads"], hp["idx_dim"], hp["rope_theta"]
        R = min(q_block, T)
        kls, sels = [], []
        for li, layer in enumerate(params["layers"]):
            h = _rms(x, layer["ln1"])
            q = _rope(_rms((h @ layer["wq"]).reshape(B, T, Hq, dh),
                           layer["q_norm"]), theta)
            k = _rope(_rms((h @ layer["wk"]).reshape(B, T, Hkv, dh),
                           layer["k_norm"]), theta)
            v = (h @ layer["wv"]).reshape(B, T, Hkv, dh)
            hs = lax.stop_gradient(h)
            qi = _rope((hs @ layer["idx_wq"]).reshape(B, T, Hi, di), theta)
            ki = _rope(_layer_norm(hs @ layer["idx_wk"], layer["idx_k_scale"],
                                   layer["idx_k_bias"]), theta)
            w = (hs @ layer["idx_ww"]) * (Hi * di) ** -0.5
            def block(i, li=li, q=q, k=k, v=v, qi=qi, ki=ki, w=w):
                b, r0 = i // (T // R), (i % (T // R)) * R

                def rows(a):
                    return lax.dynamic_slice_in_dim(a[b], r0, R, axis=0)
                given = None if selections is None else rows(selections[li])
                return _attend_block(rows(q), k[b], v[b], rows(qi), ki[b],
                                     rows(w), r0, hp["topk"], given)
            outs, kl, sel = lax.map(block, jnp.arange(B * (T // R)))
            x = x + outs.reshape(B, T, Hq * dh) @ layer["wo"]
            kls.append(kl.reshape(B, T))
            sels.append(sel.reshape(B, T, T))
            h2 = _rms(x, layer["ln2"])
            x = x + _experts(h2.reshape(B * T, D), layer, hp).reshape(B, T, D)
        logits = _rms(x, params["lnf"]) @ params["head"].T
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        kls = jnp.stack(kls)
        return {"nll": nll, "kl": kls, "selected": sels,
                "loss": jnp.mean(nll) + jnp.mean(kls)}
