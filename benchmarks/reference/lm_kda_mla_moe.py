"""Plain reference of the decoder the ``lm_kda_mla_moe`` family trains: layers
of two kinds, Kimi Delta Attention (a delta rule whose decay is per key
channel) and multi-head latent attention without positions, a leading
dense feed-forward and, after it, top-k mixtures of gated SiLU experts
under sigmoid scores of which only the ``held`` ones are computed, plus one
shared expert without a gate; untied head. Forward, loss and (through
``jax.grad``) gradients in straightforward ``jax.numpy``, float32, matmuls
at ``highest`` precision, no kernel, no chunking of the recurrence, no
sharding; written from the layers' equations (ISSUE 34, section 1; the
published form is ``modeling_kimi.py`` beside the model's config, and
arXiv:2510.26692) and NOT from ``horovod_tpu/``.

All norms: ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, plain weight. A
layer is what its leaves say: ``kda_wqkv`` a Kimi Delta Attention mixer,
``mla_wq`` a latent-attention mixer; ``router`` experts, else the dense
feed-forward. For x [T, D]:

    x += mixer(rms(x; ln1));   x += feed_forward(rms(x; ln2))

Kimi Delta Attention mixer (H heads of dh for q, k and v alike; h [T, D]):

    [q | k | v] = silu(conv(h Wqkv)),  conv_t = sum_j c[j] x_{t-3+j} a channel
    q, k: x / sqrt(sum(x^2) + 1e-6) over dh; q scaled by dh^-1/2
    g = -exp(A_log_head) softplus((h Wf_down) Wf_up + dt_bias)   [T, H, dh]
    beta = sigmoid(h Wbeta)                                       [T, H]
    per head, S [dh, dh] from zero, row by row:
        S <- Diag(exp g_t) S;  u = (v_t - S^T k_t) beta_t;  S <- S + k_t u^T
        o_t = S^T q_t
    out = (rms(o_t; w_n) * sigmoid((h Wg_down) Wg_up)) Wout

Latent attention mixer (H heads; latent rank r; key parts dn + ds; value dv):

    q = h Wq  [T, H, dn + ds];  [c | k_s] = h Wkva  [T, r + ds]
    [k_n | v]_head = rms(c; w_c) Wkvb  [T, H, dn + dv]
    P = causal softmax(q_head . [k_n_head ; k_s] / sqrt(dn + ds))
    out = concat_heads(P v_head) Wo
    (k_s is ONE vector a token, shared by the heads, and NOT rotated.)

Dense feed-forward: (silu(h Wg) * h Wu) Wd. Experts: s = sigmoid(h Wr);
E_t = top-k of s + b (b: ``router_bias``, for the selection alone);
g_e = s_e / sum_{E_t} s * scaling; the sum over e in E_t that are held of
g_e (silu(h Wg_e) * h Wu_e) Wd_e, plus (silu(h Wg_s) * h Wu_s) Wd_s. Where
experts are absent, so is the routing weights' gradient (it needs their
outputs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, remat_rows: int = 0):
    """The recurrence, token by token: q, k, g [B, T, H, dk] (g: one
    log-decay a key channel), v [B, T, H, dv], beta [B, T, H] ->
    o [B, T, H, dv]. ``remat_rows``: for a gradient over a long sequence,
    the rows are walked in stretches of so many (T a multiple) whose inner
    states the backward recomputes; the same arithmetic in the same order."""
    B, T, H, dk = q.shape

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[..., None]
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * b_t[..., None]
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    start = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    if remat_rows:
        rows = tuple(a.reshape(T // remat_rows, remat_rows, *a.shape[1:])
                     for a in rows)
        _, o = lax.scan(jax.checkpoint(
            lambda S, stretch: lax.scan(step, S, stretch)), start, rows)
        o = o.reshape(T, *o.shape[2:])
    else:
        _, o = lax.scan(step, start, rows)
    return jnp.moveaxis(o, 0, 1)


def attention(q, k, v, q_block=512):
    """Causal softmax attention, q, k [B, T, H, d], v [B, T, H, dv] ->
    [B, T, H, dv]: the scores of ``q_block`` query rows at a time (T a
    multiple of it where it is longer), scaled by d^-1/2; a block's scores
    are made again in a backward, not kept."""
    B, T, H, d = q.shape
    R = min(q_block, T)

    @jax.checkpoint
    def block(i):
        b, r0 = i // (T // R), (i % (T // R)) * R
        rows = lax.dynamic_slice_in_dim(q[b], r0, R, axis=0)
        s = jnp.einsum("rnd,snd->nrs", rows, k[b]) * d ** -0.5
        seen = jnp.arange(T)[None, :] <= (r0 + jnp.arange(R))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nrs,snd->rnd", p, v[b])
    return lax.map(block, jnp.arange(B * (T // R))).reshape(
        B, T, H, v.shape[-1])


def _delta_attention(h, layer, hp):
    """h [B, T, D] -> (the mixer's output [B, T, D], the rule's o)."""
    B, T, _ = h.shape
    H, dh = hp["kda_heads"], hp["kda_head_dim"]
    n = H * dh
    conv = layer["kda_conv"]
    width = conv.shape[0]
    x = jnp.pad(h @ layer["kda_wqkv"], ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(x[:, j:j + T] * conv[j] for j in range(width)))
    q, k, v = (qkv[..., j * n:(j + 1) * n].reshape(B, T, H, dh)
               for j in range(3))
    f = (h @ layer["kda_wf_down"]) @ layer["kda_wf_up"]
    g = -jnp.exp(layer["kda_a_log"])[:, None] * jax.nn.softplus(
        f.reshape(B, T, H, dh) + layer["kda_dt_bias"].reshape(H, dh))
    beta = jax.nn.sigmoid(h @ layer["kda_wbeta"])
    o = delta_rule(_unit(q) * dh ** -0.5, _unit(k), v, g, beta)
    gate = jax.nn.sigmoid((h @ layer["kda_wg_down"]) @ layer["kda_wg_up"])
    out = _rms(o, layer["kda_norm"], hp["eps"]) * gate.reshape(B, T, H, dh)
    return out.reshape(B, T, n) @ layer["kda_wout"], o


def _latent_attention(h, layer, hp, q_block):
    """h [B, T, D] -> (the mixer's output [B, T, D], the attention's output
    [B, T, H, dv]); the scores of ``q_block`` query rows at a time."""
    B, T, _ = h.shape
    H, r = hp["n_heads"], hp["kv_rank"]
    dn, ds, dv = hp["d_nope"], hp["d_shared"], hp["d_v"]
    q = (h @ layer["mla_wq"]).reshape(B, T, H, dn + ds)
    latent = h @ layer["mla_wkva"]
    kv = (_rms(latent[..., :r], layer["mla_kv_norm"], hp["eps"])
          @ layer["mla_wkvb"]).reshape(B, T, H, dn + dv)
    shared = jnp.broadcast_to(latent[:, :, None, r:], (B, T, H, ds))
    kk = jnp.concatenate([kv[..., :dn], shared], axis=-1)
    o = attention(q, kk, kv[..., dn:], q_block)
    return o.reshape(B, T, H * dv) @ layer["mla_wo"], o


def _gated(h2, w_gate, w_up, w_down):
    return (jax.nn.silu(h2 @ w_gate) * (h2 @ w_up)) @ w_down


def _experts(h2, layer, hp, given):
    """h2 [N, D] -> (the held experts' share plus the shared expert, the
    reference's own top-k ids [N, k]). ``given`` ([N, k] ids or None):
    the sets whose experts are computed, weighted by the reference's own
    scores renormalised over them."""
    s = jax.nn.sigmoid(h2 @ layer["router"])
    own = lax.top_k(s + layer.get("router_bias", 0.0),
                    hp["experts_per_tok"])[1]
    ids = own if given is None else given
    top = jnp.take_along_axis(s, ids, axis=-1)
    gate = top / jnp.sum(top, axis=-1, keepdims=True) * hp["scaling"]
    if layer["w_up"].shape[0] < s.shape[-1]:
        gate = lax.stop_gradient(gate)
    y = jnp.zeros_like(h2)
    for j in range(layer["w_up"].shape[0]):
        g = jnp.sum(jnp.where(ids == hp["first_expert"] + j, gate, 0.0), -1)
        y = y + g[:, None] * _gated(h2, layer["w_gate"][j], layer["w_up"][j],
                                    layer["w_down"][j])
    shared = _gated(h2, layer["shared_gate"], layer["shared_up"],
                    layer["shared_down"])
    return y + shared, own


def forward(params, tokens, labels, hp, routing=None, q_block=512):
    """``hp``: n_heads, kv_rank, d_nope, d_shared, d_v, kda_heads,
    kda_head_dim, experts_per_tok, first_expert, scaling, eps.
    ``routing``: None (the reference routes) or per EXPERT layer the
    [B*T, k] expert ids to compute. Returns {"logits", "nll" [B, T],
    "loss", "kda_o": the rule's o of each delta-attention layer, "mla_o":
    the attention's output of each latent-attention layer, "routed": the
    reference's own ids of each expert layer}. T must be a multiple of
    ``q_block`` where it is longer."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens]
        B, T, D = x.shape
        kda_o, mla_o, routed = [], [], []
        for layer in params["layers"]:
            h = _rms(x, layer["ln1"], hp["eps"])
            if "kda_wqkv" in layer:
                out, o = _delta_attention(h, layer, hp)
                kda_o.append(o)
            else:
                out, o = _latent_attention(h, layer, hp, q_block)
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
                "kda_o": kda_o, "mla_o": mla_o, "routed": routed}
