"""``mfu_pct.lm``: the traced run's tokens per second x the matmul FLOP a
token needs (lib/flops.py; recomputation not counted) over chips x the peak
of the device_kind. An end-to-end utilization on the host's clock, not a
kernel's roofline share. Layer: step builders."""

from lib import flops


def read(trace, run, cell):
    if cell["config"]["family"] != "lm":
        return {}
    tokens_per_s = run["steps"] * run["units_per_step"] / run["window_s"]
    need = flops.lm_train_flop_per_token(cell["config"], run["seq_len"])
    peak = cell["chips"] * cell["peaks"]["bf16_flop_per_s"]
    return {"mfu_pct.lm": 100.0 * tokens_per_s * need / peak}
