"""Operations the algorithms need, from shapes. Recomputation is never
counted. Copied from ``bench.py`` (``lm_train_gflop_per_token``) so that no
later PR can move the yardstick; the flash-attention count is new."""

from __future__ import annotations


def lm_train_flop_per_token(config: dict, seq_len: int) -> float:
    """Matmul FLOP to train on one token of the dense decoder: per layer
    forward 8·d² (qkv + output projection) + 4·d·ff (feed-forward) + 2·T·d
    (causal QKᵀ and AV, halved); + 2·d·V for the tied unembedding;
    training = 3 × forward."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    fwd = layers * (8 * d * d + 4 * d * ff + 2 * seq_len * d) + 2 * d * vocab
    return 3.0 * fwd


def flash_attn_flop_per_step(config: dict, batch: int, seq_len: int) -> float:
    """FLOP causal attention needs in one training step of ``batch``
    sequences on one chip, all layers: forward QKᵀ and AV are
    2 × 2·B·H·T²·d halved by causality = 2·B·H·T²·d; the backward pass
    needs twice the forward (dQ, dK, dV, and dP: four products against the
    forward's two). The kernel's own recomputation of the scores in the
    backward pass is not counted."""
    heads = config["num_attention_heads"]
    d_head = config["hidden_size"] // heads
    fwd = 2.0 * batch * heads * seq_len * seq_len * d_head
    return config["num_hidden_layers"] * 3.0 * fwd


def flash_attn_bytes_per_step(config: dict, batch: int, seq_len: int,
                              itemsize: int = 2) -> float:
    """HBM bytes the same attention has to move at the least: forward reads
    q, k, v and writes o (4 tensors of B·T·H·d); backward reads q, k, v, o,
    do and writes dq, dk, dv (8 tensors)."""
    one = batch * seq_len * config["hidden_size"] * itemsize
    return config["num_hidden_layers"] * 12.0 * one
