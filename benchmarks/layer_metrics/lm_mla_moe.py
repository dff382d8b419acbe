"""Family ``lm_mla_moe``'s per-layer metrics (nothing for another family):

* ``mfu_pct.lm_mla_moe``: the traced run's tokens per second x the FLOP a
  token needs (``lib/flops_mla_moe.lm_mla_moe_train_flop_per_token``, the
  held experts' assignments as the program counted them) over chips x the
  device_kind's peak; the host's clock. Layer: step builders.
* ``mla.rope_ms``, ``attn.mla_ms``, ``ffn.dense_ms``, ``moe.shared_ms``,
  ``moe.route_ms``, ``moe.experts_ms``: device 0's leaf ops, forward and
  backward, whose framework name carries the program's named scope of that
  name (``parallel/transformer.py``: ``mla.rope`` is the rotation of q's
  and the shared key part's rotated columns, inside ``attn.mla``;
  ``parallel/moe.py``), over the steps traced. A fusion carries its root's
  scope. Layers: attention, step builders, expert layer.
* ``mla_attend_roofline``: the least time for causal attention at the true
  widths (192 a score, 128 a value; ``lib/flops_mla_moe.py``) over the time
  of the flash kernels (``flash_fwd``, ``flash_bwd*`` by
  ``pallas_call(name=)``) under the scope ``attn.mla``. Layer: kernels.
* ``moe.load_max_over_mean``: the gauge ``hvd_moe_load_max_over_mean``
  (largest layer), stamped after the window from the parameters the last
  step left (``Family.stamp_routing``).

Where the program has no such scope, kernel or gauge, that metric is left
out."""

import os

from layer_metrics.lm_kda_mla_moe import FLASH, _least_s, gauges
from layer_metrics.lm_moe_dsa import in_scope
from lib import after_window, cell as cell_mod, flops_mla_moe as flops, \
    spans as sp, trace as tr

SCOPES = ("mla.rope", "attn.mla", "ffn.dense", "moe.shared", "moe.route",
          "moe.experts")


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


def read(trace, run, cell):
    config = cell["config"]
    if config["family"] != "lm_mla_moe":
        return {}
    out = {}
    for hook in after_window.HOOKS:
        hook()
    found = gauges()
    load = found.get("hvd_moe_load_max_over_mean")
    if load:
        out["moe.load_max_over_mean"] = max(load.values())

    held = found.get("hvd_moe_held_assignments")
    absent = found.get("hvd_moe_absent_assignments")
    per_token = None
    if held and absent:
        per_token = config["num_experts_per_tok"] * sum(held.values()) / (
            sum(held.values()) + sum(absent.values()))
    tokens_per_s = run["steps"] * run["units_per_step"] / run["window_s"]
    need = flops.lm_mla_moe_train_flop_per_token(config, run["seq_len"],
                                                 per_token)
    out["mfu_pct.lm_mla_moe"] = 100.0 * tokens_per_s * need / (
        cell["chips"] * cell["peaks"]["bf16_flop_per_s"])

    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    scoped = by_scope(tr.leaf_ops(trace.devices[0]),
                      sp.framework_names(xplane), run["steps"])
    flash_ms = scoped.pop("flash", None)
    out.update({scope + "_ms": ms for scope, ms in scoped.items()})
    if flash_ms:
        args = (config, run["batch_per_chip"], run["seq_len"])
        out["mla_attend_roofline"] = 100.0 * _least_s(
            flops.mla_attend_flop_per_step(*args),
            flops.mla_attend_bytes_per_step(*args),
            cell["peaks"]) / (flash_ms / 1e3)
    return out
