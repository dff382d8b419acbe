"""``flash_attn.ms_per_step`` and ``flash_attn_roofline``: the Pallas flash
kernels of ``ops/pallas_attention.py`` (forward, fused or split backward) on
device 0. Time is the sum of the kernels' device durations over the steps
traced. The roofline share is the least time the chip could take for the
attention the step needs (lib/flops.py: the larger of FLOP over peak FLOP/s
and bytes over peak bytes/s, no recomputation counted) over that time.
Layer: kernels."""

from lib import flops, trace as tr


def is_kernel(name: str) -> bool:
    """A Mosaic kernel's own instruction. The LM step has no other Pallas
    kernel than the flash kernels (the kernels carry no ``name=`` yet:
    PERF.md, for the tracing issue). Not a substring of the whole text: the
    kernels' consumers name ``%pallas_call.N`` among their operands."""
    return tr.custom_call_target(name) == "tpu_custom_call"


def read(trace, run, cell):
    if cell["config"]["family"] != "lm":
        return {}
    ns = tr.matching_ns(trace.devices[0], is_kernel)
    if not ns:
        return {}
    seconds_per_step = ns / 1e9 / run["steps"]
    args = (cell["config"], run["batch_per_chip"], run["seq_len"])
    least = max(flops.flash_attn_flop_per_step(*args)
                / cell["peaks"]["bf16_flop_per_s"],
                flops.flash_attn_bytes_per_step(*args)
                / cell["peaks"]["hbm_bytes_per_s"])
    return {"flash_attn.ms_per_step": seconds_per_step * 1e3,
            "flash_attn_roofline": 100.0 * least / seconds_per_step}
