"""Family ``lm_kda_mla_moe``'s per-layer metrics (nothing for another
family):

* ``mfu_pct.lm_kda_mla_moe``: the traced run's tokens per second x the FLOP
  a token needs (``lib/flops_kda_mla_moe.lm_kda_mla_moe_train_flop_per_token``,
  the held experts' assignments as the program counted them) over chips x
  the device_kind's peak; the host's clock. Layer: step builders.
* ``kda.proj_ms``, ``kda.conv_ms``, ``kda.scan_ms``, ``kda.out_ms``,
  ``attn.mla_ms``, ``ffn.dense_ms``, ``moe.shared_ms``, ``moe.route_ms``,
  ``moe.experts_ms``: device 0's leaf ops, forward and backward, whose
  framework name carries the program's named scope of that name
  (``parallel/transformer.py``, ``parallel/moe.py``), over the steps
  traced. Layers: linear attention, attention, step builders, expert layer.
* ``kda_scan_roofline``: the least time the chip could take for the delta
  rule with a decay per channel (``lib/flops_kda_mla_moe.py``: the
  recurrence's own FLOP and the bytes of its inputs, outputs and gradients,
  from the configuration and the lengths alone) over the time of EVERY
  device op under the scope ``kda.scan`` (norms, gates, the chunk-local
  stage, the walk, forward and backward), so that it reads the same work
  whichever backend and chunk length run. ``mla_attend_roofline``: the
  least time for causal attention at the true widths (192 a score, 128 a
  value) over the time of the flash kernels (``flash_fwd``, ``flash_bwd*``
  by ``pallas_call(name=)``) under the scope ``attn.mla``. Layer: kernels.
* ``kda.saved_state_mb``: the program's gauge ``hvd_kda_saved_state_bytes``
  (what the rule's custom VJP keeps for the backward), summed over the
  layers. ``moe.load_max_over_mean``: the gauge
  ``hvd_moe_load_max_over_mean`` (largest layer), stamped after the window
  from the parameters the last step left (``Family.stamp_routing``).

Where the program has no such scope, kernel or gauge, that metric is left
out."""

import os

from layer_metrics.lm_moe_dsa import in_scope
from lib import after_window, cell as cell_mod, flops_kda_mla_moe as flops, \
    spans as sp, trace as tr

SCOPES = ("kda.proj", "kda.conv", "kda.scan", "kda.out", "attn.mla",
          "ffn.dense", "moe.shared", "moe.route", "moe.experts")
FLASH = ("flash_fwd", "flash_bwd")


def by_scope(ops, names, steps):
    """{scope: ms a step} of the leaf ops ``(name, start, end)`` whose
    framework name (``names``) carries one of ``SCOPES``, and under
    ``"flash"`` those of the flash kernels under ``attn.mla``."""
    total = dict.fromkeys(SCOPES + ("flash",), 0.0)
    for name, start, end in ops:
        text = names.get(name, "")
        for scope in SCOPES:
            if in_scope(text, scope):
                total[scope] += end - start
        if in_scope(text, "attn.mla") \
                and tr.short_name(name).startswith(FLASH):
            total["flash"] += end - start
    return {s: ns / 1e6 / steps for s, ns in total.items() if ns}


def gauges():
    """{name: {layer: value}} of the program's routing and rule gauges that
    have been stamped."""
    try:
        from horovod_tpu.obs.registry import parse_exposition, registry
        samples = parse_exposition(registry().render())
    except (ImportError, AttributeError):
        return {}
    out = {}
    for (name, labels), value in samples.items():
        if name.startswith(("hvd_moe_", "hvd_kda_")):
            out.setdefault(name, {})[dict(labels).get("layer")] = value
    return out


def _least_s(flop, bytes_, peaks):
    return max(flop / peaks["bf16_flop_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def read(trace, run, cell):
    config = cell["config"]
    if config["family"] != "lm_kda_mla_moe":
        return {}
    out = {}
    for hook in after_window.HOOKS:
        hook()
    found = gauges()
    load = found.get("hvd_moe_load_max_over_mean")
    if load:
        out["moe.load_max_over_mean"] = max(load.values())
    saved = found.get("hvd_kda_saved_state_bytes")
    if saved:
        out["kda.saved_state_mb"] = sum(saved.values()) / 1e6

    held = found.get("hvd_moe_held_assignments")
    absent = found.get("hvd_moe_absent_assignments")
    per_token = None
    if held and absent:
        per_token = config["num_experts_per_token"] * sum(held.values()) / (
            sum(held.values()) + sum(absent.values()))
    tokens_per_s = run["steps"] * run["units_per_step"] / run["window_s"]
    need = flops.lm_kda_mla_moe_train_flop_per_token(
        config, run["seq_len"], per_token)
    out["mfu_pct.lm_kda_mla_moe"] = 100.0 * tokens_per_s * need / (
        cell["chips"] * cell["peaks"]["bf16_flop_per_s"])

    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    scoped = by_scope(tr.leaf_ops(trace.devices[0]),
                      sp.framework_names(xplane), run["steps"])
    flash_ms = scoped.pop("flash", None)
    out.update({scope + "_ms": ms for scope, ms in scoped.items()})
    args = (config, run["batch_per_chip"], run["seq_len"])
    if "kda.scan" in scoped:
        out["kda_scan_roofline"] = 100.0 * _least_s(
            flops.kda_rule_flop_per_step(*args),
            flops.kda_rule_bytes_per_step(*args),
            cell["peaks"]) / (scoped["kda.scan"] / 1e3)
    if flash_ms:
        out["mla_attend_roofline"] = 100.0 * _least_s(
            flops.mla_attend_flop_per_step(*args),
            flops.mla_attend_bytes_per_step(*args),
            cell["peaks"]) / (flash_ms / 1e3)
    return out
