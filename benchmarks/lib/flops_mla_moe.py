"""Operations and bytes the ``lm_mla_moe`` family's algorithms need, from the
configuration and the lengths alone: latent attention on every layer, a
leading dense feed-forward, and a share of a mixture of experts with shared
experts. Counted as ``lib/flops_kda_mla_moe.py`` counts them, whose latent
attention's functions this file reads through: causal attention at the
heads' true widths (192 for a score, 128 for a value), never a padded
operand's, so that the kernels' padding of q and k to 256 shows as lost
roofline; held assignments as counted; no recomputation, whatever
implements it. The rotation (a few elementwise operations a rotated
column) is not counted."""

from __future__ import annotations

from lib import flops_kda_mla_moe as kimi


def _all_latent(config: dict) -> dict:
    """The configuration as ``flops_kda_mla_moe`` reads layers: every layer
    held is latent attention."""
    return dict(config, linear_attn_config={
        "kda_layers": [],
        "full_attn_layers": list(range(1, config["num_hidden_layers"] + 1))})


def mla_attend_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """Causal attention of every layer in one training step: forward
    2 (dn + dr) for a score and 2 dv for its value a pair and head,
    backward twice that."""
    return kimi.mla_attend_flop_per_step(_all_latent(config), batch, seq_len)


def mla_attend_bytes_per_step(config: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> float:
    """HBM bytes the same attention moves at the least (forward reads q, k,
    v and writes o; backward reads them and do, writes dq, dk, dv)."""
    return kimi.mla_attend_bytes_per_step(_all_latent(config), batch,
                                          seq_len, itemsize)


def lm_mla_moe_train_flop_per_token(config: dict, seq_len: int,
                                    assignments_per_token=None) -> float:
    """FLOP to train on one token. Forward, a latent-attention layer: q
    2 d H (dn + dr), the latent and the shared key part 2 d (r + dr), its
    expansion 2 r H (dn + dv), the output projection 2 H dv d, causal QK^T
    and PV (2 (dn + dr) + 2 dv) H (T + 1) / 2. The leading dense layers:
    6 d F. Every other layer: the router 2 d E, the shared experts
    6 d Fe n_shared, three d x Fe products for each assignment to an expert
    held here (``assignments_per_token``: as counted, or what a balanced
    router sends, k held / E). Once: 2 d rows for the head over the rows of
    the vocabulary held. Training = 3 x forward."""
    d, h, r = config["hidden_size"], config["num_attention_heads"], \
        config["kv_lora_rank"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], \
        config["v_head_dim"]
    fe, routed = config["moe_intermediate_size"], config["router_experts"]
    if assignments_per_token is None:
        assignments_per_token = config["num_experts_per_tok"] \
            * config["n_routed_experts"] / routed
    mla = (2 * d * h * (dn + dr) + 2 * d * (r + dr) + 2 * r * h * (dn + dv)
           + 2 * h * dv * d
           + (2 * (dn + dr) + 2 * dv) * h * kimi._pairs(seq_len) / seq_len)
    experts = (2 * d * routed + 6 * d * fe * config["n_shared_experts"]
               + assignments_per_token * 6 * d * fe)
    n = config["num_hidden_layers"]
    n_dense = min(config["first_k_dense_replace"], n)
    fwd = (n * mla + n_dense * 6 * d * config["intermediate_size"]
           + (n - n_dense) * experts + 2 * d * config["vocab_rows_held"])
    return 3.0 * fwd
