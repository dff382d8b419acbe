"""Operations and bytes the ``lm_gdn_moe`` family's algorithms need, from
the configuration and the lengths alone: layers of Gated DeltaNet linear
attention and of gated softmax attention, and a share of a mixture of
experts with a shared expert. Counted is the work the equations need — the
recurrence as it is defined, row by row, never a chunked form's products;
held assignments as counted — and no recomputation, whatever implements it.
(``lib/flops.py`` and ``lib/flops_moe_dsa.py`` have the other families'.)"""

from __future__ import annotations


def layer_counts(config: dict):
    """(Gated DeltaNet layers, full-attention layers): layer i (from 0) is
    full iff (i + 1) % full_attention_interval == 0."""
    n, every = config["num_hidden_layers"], config["full_attention_interval"]
    full = n // every
    return n - full, full


def gdn_rule_flop_per_token(config: dict) -> float:
    """The recurrence, forward, one layer: per value head ``7 dk dv`` (the
    decay of S: dk dv; S^T k, the rank-one update and S^T q: 2 dk dv
    each)."""
    return 7.0 * config["linear_num_value_heads"] \
        * config["linear_key_head_dim"] * config["linear_value_head_dim"]


def lm_gdn_moe_train_flop_per_token(config: dict, seq_len: int,
                                    assignments_per_token=None) -> float:
    """FLOP to train on one token. Forward, a Gated DeltaNet layer: the
    input projections 2 d (2 Hk dk + 2 Hv dv + 2 Hv), the convolution
    2 W (2 Hk dk + Hv dv), the rule (above), the output projection
    2 Hv dv d. A full-attention layer: q with its gate, k, v and the output
    projection 2 d (3 H dh + 2 Hkv dh), causal QK^T and PV 4 H dh (T + 1) / 2.
    Every layer: the router 2 d E, the shared expert 6 d Fs + 2 d, three
    d x F products for each assignment to an expert held here
    (``assignments_per_token``: as counted, or what a balanced router
    sends, k held / E). Once: 2 d rows for the head over the rows of the
    vocabulary held. Training = 3 x forward."""
    d = config["hidden_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    h, hkv, dh = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    routed, held = config["router_experts"], config["num_experts"]
    if assignments_per_token is None:
        assignments_per_token = config["num_experts_per_tok"] * held / routed
    gdn = (2 * d * (2 * hk * dk + 2 * hv * dv + 2 * hv)
           + 2 * config["linear_conv_kernel_dim"] * (2 * hk * dk + hv * dv)
           + gdn_rule_flop_per_token(config) + 2 * hv * dv * d)
    full = (2 * d * (3 * h * dh + 2 * hkv * dh)
            + 4 * h * dh * (seq_len + 1) / 2)
    experts = (2 * d * routed
               + 6 * d * config["shared_expert_intermediate_size"] + 2 * d
               + assignments_per_token * 6 * d
               * config["moe_intermediate_size"])
    n_gdn, n_full = layer_counts(config)
    fwd = (n_gdn * gdn + n_full * full
           + config["num_hidden_layers"] * experts
           + 2 * d * config["vocab_rows_held"])
    return 3.0 * fwd


def gdn_rule_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """FLOP the recurrence needs in one training step, all Gated DeltaNet
    layers: forward ``7 dk dv`` a token and value head, backward twice
    that."""
    return layer_counts(config)[0] * 3.0 * batch * seq_len \
        * gdn_rule_flop_per_token(config)


def gdn_rule_bytes_per_step(config: dict, batch: int, seq_len: int,
                            itemsize: int = 2) -> float:
    """HBM bytes the same recurrence moves at the least. A token's row:
    q and k of every key head (dk each), v of every value head (dv), g and
    beta in float32, and o (dv a value head). Forward reads q, k, v, g,
    beta and writes o. Backward reads them again with do in o's place, and
    writes the gradients of q, k, v, g and beta."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    inputs = (2 * hk * dk + hv * dv) * itemsize + 2 * hv * 4
    out = hv * dv * itemsize
    row = (inputs + out) + (inputs + out) + inputs
    return layer_counts(config)[0] * batch * seq_len * float(row)
