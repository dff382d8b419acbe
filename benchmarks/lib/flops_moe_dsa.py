"""Operations the ``lm_moe_dsa`` family's algorithms need, from shapes:
sparse attention over indexer-selected keys, and a share of a mixture of
experts. Counted is the work the equations need — selected pairs, held
assignments as counted — and no recomputation, whatever implements it.
(``lib/flops.py`` has the dense families' counts.)"""

from __future__ import annotations


def selected_pairs_per_row(topk: int, seq_len: int) -> float:
    """Mean over the rows t of a sequence of min(topk, t + 1)."""
    k = min(topk, seq_len)
    return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len


def lm_moe_dsa_train_flop_per_token(config: dict, seq_len: int,
                                    assignments_per_token=None) -> float:
    """Matmul FLOP to train on one token. Per layer, forward: the q, k, v
    and output projections 2·d·(2·Hq·dh + 2·Hkv·dh); the indexer's
    projections 2·d·(Hi·di + di + Hi) and its scores 2·Hi·di for each
    causal pair ((T+1)/2 a row); QKᵀ and PV over the selected pairs,
    4·Hq·dh each; the router 2·d·E; three d x F products for each
    assignment to an expert held here (``assignments_per_token``: as
    counted, or what a balanced router sends, k·held/E). Once: 2·d·rows for
    the head over the rows of the vocabulary held. Training = 3 x forward
    (the KL's head-mean of P is the forward's own P: nothing more)."""
    d, dh = config["hidden_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    routed, held = config["num_local_experts"], config["num_experts"]
    if assignments_per_token is None:
        assignments_per_token = config["num_experts_per_tok"] * held / routed
    layer = (2 * d * (2 * hq * dh + 2 * hkv * dh)
             + 2 * d * (hi * di + di + hi)
             + 2 * hi * di * (seq_len + 1) / 2
             + 4 * hq * dh * selected_pairs_per_row(sa["topk"], seq_len)
             + 2 * d * routed
             + assignments_per_token * 6 * d * config["moe_intermediate_size"])
    fwd = config["num_hidden_layers"] * layer \
        + 2 * d * config["vocab_rows_held"]
    return 3.0 * fwd


def dsa_attend_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """FLOP that attention over the selected pairs needs in one training
    step, all layers: forward QKᵀ and PV, 4·Hq·dh a pair; backward twice
    that. Unselected pairs a kernel computes and masks are not counted."""
    pairs = batch * seq_len * selected_pairs_per_row(
        config["sa_config"]["topk"], seq_len)
    return config["num_hidden_layers"] * 3.0 * pairs * 4 \
        * config["num_attention_heads"] * config["head_dim"]


def dsa_attend_bytes_per_step(config: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> float:
    """HBM bytes the same attention moves at the least: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv
    (q-sized: Hq·dh a row, k-sized: Hkv·dh); and the selection is read
    once each way, one bit a causal pair."""
    rows = batch * seq_len
    q_row = config["num_attention_heads"] * config["head_dim"] * itemsize
    kv_row = config["num_key_value_heads"] * config["head_dim"] * itemsize
    selection = 2 * batch * seq_len * (seq_len + 1) / 2 / 8
    return config["num_hidden_layers"] * (
        rows * (6 * q_row + 6 * kv_row) + selection)
