"""The FLOP and byte functions against numbers worked by hand."""

import json
import os

import pytest

from bench_paths import BENCH
from lib import flops

with open(os.path.join(BENCH, "configs", "lm_pythia14b_width.json")) as fh:
    LM = json.load(fh)


def test_lm_train_flop_per_token_at_the_cell_size():
    # per layer: 8*2048^2 = 33,554,432; 4*2048*8192 = 67,108,864;
    # 2*2048*2048 = 8,388,608 -> 109,051,904; x 8 = 872,415,232;
    # unembed 2*2048*50304 = 206,045,184 -> forward 1,078,460,416; x 3.
    assert flops.lm_train_flop_per_token(LM, 2048) == 3 * 1_078_460_416


def test_lm_flop_per_token_tiny():
    c = {"hidden_size": 2, "intermediate_size": 3, "num_hidden_layers": 1,
         "vocab_size": 5}
    # 8*4 + 4*2*3 + 2*7*2 = 84; + 2*2*5 = 104; x 3
    assert flops.lm_train_flop_per_token(c, 7) == 312


def test_flash_attention_flop_and_bytes_at_the_cell_size():
    # forward 2*B*H*T^2*d = 2*8*16*2048^2*128 = 137,438,953,472 a layer;
    # x 3 (backward is twice the forward) x 8 layers.
    assert flops.flash_attn_flop_per_step(LM, 8, 2048) == \
        8 * 3 * 137_438_953_472
    # one [B, T, D] bf16 tensor = 8*2048*2048*2 = 67,108,864 bytes; 12 of
    # them a layer (4 forward, 8 backward) x 8 layers.
    assert flops.flash_attn_bytes_per_step(LM, 8, 2048) == \
        8 * 12 * 67_108_864


def test_peaks_are_keyed_by_device_kind_with_a_source():
    with open(os.path.join(BENCH, "lib", "peaks.json")) as fh:
        peaks = json.load(fh)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flop_per_s"] == pytest.approx(197e12)
    assert v5e["hbm_bytes_per_s"] == pytest.approx(819e9)
    assert all("source" in p for p in peaks.values())
