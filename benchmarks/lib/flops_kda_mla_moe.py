"""Operations and bytes the ``lm_kda_mla_moe`` family's algorithms need, from
the configuration and the lengths alone: layers of Kimi Delta Attention
(a delta rule with a decay per key channel) and of latent attention, a
leading dense feed-forward, and a share of a mixture of experts with a
shared expert. Counted is the work the equations need — the recurrence as
it is defined, row by row, never a chunked form's products; causal
attention at the heads' true widths (192 for a score, 128 for a value),
never a padded operand's; held assignments as counted — and no
recomputation, whatever implements it. (``lib/flops.py``,
``lib/flops_moe_dsa.py`` and ``lib/flops_gdn_moe.py`` have the other
families'.)"""

from __future__ import annotations


def layer_counts(config: dict):
    """(delta-attention layers, latent-attention layers) among the layers
    held: layer i (from 1) is what ``linear_attn_config``'s lists say."""
    lin, n = config["linear_attn_config"], config["num_hidden_layers"]
    kda = sum(1 for i in lin["kda_layers"] if i <= n)
    full = sum(1 for i in lin["full_attn_layers"] if i <= n)
    if kda + full != n:
        raise ValueError(f"linear_attn_config's lists name {kda} + {full} "
                         f"of the {n} layers held")
    return kda, full


def kda_rule_flop_per_token(config: dict) -> float:
    """The recurrence, forward, one layer: per head ``7 dk dv`` (the decay
    of S: dk dv; S^T k, the rank-one update and S^T q: 2 dk dv each)."""
    lin = config["linear_attn_config"]
    return 7.0 * lin["num_heads"] * lin["head_dim"] * lin["head_dim"]


def _pairs(seq_len: int) -> float:
    """Causal (query, key) pairs a sequence."""
    return seq_len * (seq_len + 1) / 2


def lm_kda_mla_moe_train_flop_per_token(config: dict, seq_len: int,
                                        assignments_per_token=None) -> float:
    """FLOP to train on one token. Forward, a delta-attention layer: q, k
    and v 2 d 3n (n = H dh), the two low-rank pairs 2 (2 d r + 2 r n) with
    r = dh, beta 2 d H, the convolution 2 W 3n, the rule (above), the
    output projection 2 n d. A latent-attention layer: q 2 d H (dn + ds),
    the latent and the shared key part 2 d (r + ds), its expansion
    2 r H (dn + dv), the output projection 2 H dv d, causal QK^T and PV
    (2 (dn + ds) + 2 dv) H (T + 1) / 2. The leading dense layers: 6 d F.
    Every other layer: the router 2 d E, the shared expert 6 d Fs, three
    d x Fe products for each assignment to an expert held here
    (``assignments_per_token``: as counted, or what a balanced router
    sends, k held / E). Once: 2 d rows for the head over the rows of the
    vocabulary held. Training = 3 x forward."""
    d, lin = config["hidden_size"], config["linear_attn_config"]
    hk, dh, w = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    n = hk * dh
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    dn, ds, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], \
        config["v_head_dim"]
    routed, held = config["router_experts"], config["num_experts"]
    if assignments_per_token is None:
        assignments_per_token = config["num_experts_per_token"] * held \
            / routed
    kda = (2 * d * 3 * n + 2 * (2 * d * dh + 2 * dh * n) + 2 * d * hk
           + 2 * w * 3 * n + kda_rule_flop_per_token(config) + 2 * n * d)
    mla = (2 * d * h * (dn + ds) + 2 * d * (r + ds) + 2 * r * h * (dn + dv)
           + 2 * h * dv * d
           + (2 * (dn + ds) + 2 * dv) * h * _pairs(seq_len) / seq_len)
    dense = 6 * d * config["intermediate_size"]
    experts = (2 * d * routed
               + 6 * d * config["moe_intermediate_size"]
               * config["num_shared_experts"]
               + assignments_per_token * 6 * d
               * config["moe_intermediate_size"])
    n_kda, n_mla = layer_counts(config)
    n_dense = min(config["first_k_dense_replace"],
                  config["num_hidden_layers"])
    fwd = (n_kda * kda + n_mla * mla + n_dense * dense
           + (config["num_hidden_layers"] - n_dense) * experts
           + 2 * d * config["vocab_rows_held"])
    return 3.0 * fwd


def kda_rule_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """FLOP the recurrence needs in one training step, all delta-attention
    layers: forward ``7 dk dv`` a token and head, backward twice that."""
    return layer_counts(config)[0] * 3.0 * batch * seq_len \
        * kda_rule_flop_per_token(config)


def kda_rule_bytes_per_step(config: dict, batch: int, seq_len: int,
                            itemsize: int = 2, gate_itemsize: int = 4
                            ) -> float:
    """HBM bytes the same recurrence moves at the least. A token's row:
    q, k and v of every head (dh each), g at dh values a head in the dtype
    the program hands the rule (``gate_itemsize``), beta in float32, and o.
    Forward reads q, k, v, g, beta and writes o. Backward reads them again
    with do in o's place, and writes the five gradients."""
    lin = config["linear_attn_config"]
    n = lin["num_heads"] * lin["head_dim"]
    inputs = 3 * n * itemsize + n * gate_itemsize + lin["num_heads"] * 4
    out = n * itemsize
    row = (inputs + out) + (inputs + out) + inputs
    return layer_counts(config)[0] * batch * seq_len * float(row)


def mla_attend_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """Causal attention of the latent-attention layers in one training
    step, at the true widths: forward 2 (dn + ds) for a score and 2 dv for
    its value a pair and head, backward twice that."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return layer_counts(config)[1] * 3.0 * batch \
        * config["num_attention_heads"] * _pairs(seq_len) * 2 * width


def mla_attend_bytes_per_step(config: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> float:
    """HBM bytes the same attention moves at the least, a token and head:
    forward reads q, k (dn + ds) and v and writes o (dv); backward reads
    q, k, v, o and do and writes dq, dk, dv."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    row = (2 * qk + 2 * dv) + (2 * qk + 3 * dv) + (2 * qk + dv)
    return layer_counts(config)[1] * batch * seq_len \
        * config["num_attention_heads"] * float(row * itemsize)
