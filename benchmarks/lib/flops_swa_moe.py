"""Operations and bytes the ``lm_swa_moe`` family's algorithms need, from the
configuration and the lengths alone: grouped-query attention over a sliding
window on some layers and over the whole causal past on the others, each
with its gate, a leading dense feed-forward, and a share of a mixture of
experts with a shared expert. Counted is the work the equations need: a
window layer's attention over the (query, key) pairs inside its band
alone, never a tile the kernels compute whole at the band's edge; held
assignments as counted; no recomputation, whatever implements it. Norms,
the rotation and the gate's sigmoid (a few elementwise operations a
column) are not counted. (``lib/flops_mla_moe.py`` and the files it reads
have the other MoE families'.)"""

from __future__ import annotations

from lib import flops_kda_mla_moe as kimi


def layer_counts(config: dict):
    """(window layers, full layers) among the layers held: the published
    ``layer_types`` of ``layers_held``."""
    kinds = [config["layer_types"][i] for i in config["layers_held"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def band_pairs(seq_len: int, window: int) -> float:
    """(query, key) pairs a sequence in a window of ``window`` keys, each
    query's own among them."""
    w = min(window, seq_len)
    return w * (w + 1) / 2 + (seq_len - w) * w


def swa_attend_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """The window layers' attention in one training step: forward 4 dh a
    pair and query head (QK^T and PV), backward twice that."""
    return layer_counts(config)[0] * 3.0 * batch \
        * config["num_attention_heads"] * 4 * config["head_dim"] \
        * band_pairs(seq_len, config["sliding_window"])


def swa_attend_bytes_per_step(config: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> float:
    """HBM bytes the same attention moves at the least, a token: forward
    reads q (H heads), k and v (G heads) and writes o (H); backward reads
    them and do (H) and writes dq (H), dk and dv (G)."""
    h, g = config["num_attention_heads"], config["num_key_value_heads"]
    row = (2 * h + 2 * g) + (3 * h + 2 * g) + (h + 2 * g)
    return layer_counts(config)[0] * batch * seq_len \
        * float(row * config["head_dim"] * itemsize)


def lm_swa_moe_train_flop_per_token(config: dict, seq_len: int,
                                    assignments_per_token=None) -> float:
    """FLOP to train on one token. Forward, every layer's attention: q and
    its gate 2 d 2 H dh, k and v 2 d 2 G dh, the output projection 2 H dh
    d; QK^T and PV 4 H dh a pair: a window layer's pairs of its band, a
    full layer's causal (T + 1) / 2. The leading dense layers: 6 d F.
    Every other layer: the router 2 d E, the shared expert 6 d Fe n_shared,
    three d x Fe products for each assignment to an expert held here
    (``assignments_per_token``: as counted, or what a balanced router
    sends, k held / E). Once: 2 d rows for the head over the rows of the
    vocabulary held. Training = 3 x forward."""
    d, h, g = config["hidden_size"], config["num_attention_heads"], \
        config["num_key_value_heads"]
    dh, fe = config["head_dim"], config["moe_intermediate_size"]
    routed = config["router_experts"]
    if assignments_per_token is None:
        assignments_per_token = config["num_experts_per_tok"] \
            * config["num_experts"] / routed
    proj = 2 * d * 2 * h * dh + 2 * d * 2 * g * dh + 2 * h * dh * d
    pair = 4 * h * dh
    window = proj + pair * band_pairs(seq_len, config["sliding_window"]) \
        / seq_len
    full = proj + pair * kimi._pairs(seq_len) / seq_len
    experts = (2 * d * routed + 6 * d * fe * config["num_shared_experts"]
               + assignments_per_token * 6 * d * fe)
    n_window, n_full = layer_counts(config)
    n_dense = sum(1 for i in config["layers_held"]
                  if i < config["num_dense_layers"])
    n = config["num_hidden_layers"]
    fwd = (n_window * window + n_full * full
           + n_dense * 6 * d * config["intermediate_size"]
           + (n - n_dense) * experts + 2 * d * config["vocab_rows_held"])
    return 3.0 * fwd
