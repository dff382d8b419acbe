"""Family ``lm_swa_moe``'s per-layer metrics (nothing for another family):

* ``mfu_pct.lm_swa_moe``: the traced run's tokens per second x the FLOP a
  token needs (``lib/flops_swa_moe.lm_swa_moe_train_flop_per_token``: a
  window layer's pairs of its band alone, the held experts' assignments as
  the program counted them) over chips x the device_kind's peak; the
  host's clock. Layer: step builders.
* ``attn.swa_ms``, ``attn.full_ms``, ``ffn.dense_ms``, ``moe.shared_ms``,
  ``moe.route_ms``, ``moe.experts_ms``: device 0's leaf ops, forward and
  backward, whose framework name carries the program's named scope of that
  name (``parallel/transformer.py``: ``attn.swa`` is a window layer's whole
  mixer, projections, q/k norm, RoPE, the kernels, the gate and the output
  projection; ``attn.full`` the full layer's; ``parallel/moe.py``), over
  the steps traced. A fusion carries its root's scope. Layers: attention,
  step builders, expert layer.
* ``swa_attend_roofline``: the least time for the window layers' attention
  over the pairs in their band (``lib/flops_swa_moe.py``: forward 4 dh a
  pair and head, backward twice that) over the time of the flash kernels
  (``flash_fwd``, ``flash_bwd*`` by ``pallas_call(name=)``) under the scope
  ``attn.swa``. Layer: kernels.
* ``swa.tiles_visited_pct``: the gauges ``hvd_swa_tile_pairs{kind=}``
  (stamped from the kernels' own schedule when the step was traced),
  visited over causal, summed over the window layers. Layer: attention.
* ``moe.load_max_over_mean``: the gauge ``hvd_moe_load_max_over_mean``
  (largest layer), stamped after the window from the parameters the last
  step left (``Family.stamp_routing``).

Where the program has no such scope, kernel or gauge, that metric is left
out."""

import os

from layer_metrics.lm_kda_mla_moe import FLASH, _least_s, gauges
from layer_metrics.lm_moe_dsa import in_scope
from lib import after_window, cell as cell_mod, flops_swa_moe as flops, \
    spans as sp, trace as tr

SCOPES = ("attn.swa", "attn.full", "ffn.dense", "moe.shared", "moe.route",
          "moe.experts")


def by_scope(ops, names, steps, scopes=SCOPES, kernels_in="attn.swa"):
    """{scope: ms a step} of the leaf ops ``(name, start, end)`` whose
    framework name (``names``) carries one of ``scopes``, and under
    ``"flash"`` those of the flash kernels under ``kernels_in``."""
    total = dict.fromkeys(scopes + ("flash",), 0.0)
    for name, start, end in ops:
        text = names.get(name, "")
        for scope in scopes:
            if in_scope(text, scope):
                total[scope] += end - start
        if in_scope(text, kernels_in) \
                and tr.short_name(name).startswith(FLASH):
            total["flash"] += end - start
    return {s: ns / 1e6 / steps for s, ns in total.items() if ns}


def tile_pairs():
    """{kind: summed over the layers} of the gauge ``hvd_swa_tile_pairs``,
    or {} where it was never stamped."""
    try:
        from horovod_tpu.obs.registry import parse_exposition, registry
        samples = parse_exposition(registry().render())
    except (ImportError, AttributeError):
        return {}
    out = {}
    for (name, labels), value in samples.items():
        if name == "hvd_swa_tile_pairs":
            kind = dict(labels).get("kind")
            out[kind] = out.get(kind, 0.0) + value
    return out


def read(trace, run, cell):
    config = cell["config"]
    if config["family"] != "lm_swa_moe":
        return {}
    out = {}
    for hook in after_window.HOOKS:
        hook()
    found = gauges()
    load = found.get("hvd_moe_load_max_over_mean")
    if load:
        out["moe.load_max_over_mean"] = max(load.values())
    pairs = tile_pairs()
    if pairs.get("causal"):
        out["swa.tiles_visited_pct"] = 100.0 * pairs.get("visited", 0.0) \
            / pairs["causal"]

    held = found.get("hvd_moe_held_assignments")
    absent = found.get("hvd_moe_absent_assignments")
    per_token = None
    if held and absent:
        per_token = config["num_experts_per_tok"] * sum(held.values()) / (
            sum(held.values()) + sum(absent.values()))
    tokens_per_s = run["steps"] * run["units_per_step"] / run["window_s"]
    need = flops.lm_swa_moe_train_flop_per_token(config, run["seq_len"],
                                                 per_token)
    out["mfu_pct.lm_swa_moe"] = 100.0 * tokens_per_s * need / (
        cell["chips"] * cell["peaks"]["bf16_flop_per_s"])

    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    scoped = by_scope(tr.leaf_ops(trace.devices[0]),
                      sp.framework_names(xplane), run["steps"])
    flash_ms = scoped.pop("flash", None)
    out.update({scope + "_ms": ms for scope, ms in scoped.items()})
    if flash_ms:
        args = (config, run["batch_per_chip"], run["seq_len"])
        out["swa_attend_roofline"] = 100.0 * _least_s(
            flops.swa_attend_flop_per_step(*args),
            flops.swa_attend_bytes_per_step(*args),
            cell["peaks"]) / (flash_ms / 1e3)
    return out
