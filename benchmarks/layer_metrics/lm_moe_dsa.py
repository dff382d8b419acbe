"""Family ``lm_moe_dsa``'s per-layer metrics (nothing for another family):

* ``mfu_pct.lm_moe_dsa``: the traced run's tokens per second x the FLOP a
  token needs (``lib/flops_moe_dsa.lm_moe_dsa_train_flop_per_token``, the
  held experts' assignments as the program counted them) over chips x the
  device_kind's peak; the host's clock. Layer: step builders.
* ``dsa.indexer_ms``, ``dsa.select_ms``, ``dsa.attend_ms``,
  ``dsa.indexer_loss_ms``, ``moe.route_ms``, ``moe.experts_ms``: device 0's
  leaf ops, forward and backward, whose framework name carries the
  program's named scope ``attn.indexer``, ``attn.select``, ``attn.sparse``,
  ``attn.indexer_loss``, ``moe.route``, ``moe.experts``
  (``ops/sparse_attention.py``, ``parallel/moe.py``), over the steps
  traced. Layers: sparse attention, expert layer.
* ``dsa_attend_roofline``: the least time the chip could take for attention
  over the selected pairs (``lib/flops_moe_dsa.py``) over the time of the
  kernels ``dsa_fwd``, ``dsa_bwd_dq`` and ``dsa_bwd_dkv``, found by
  ``pallas_call(name=)`` (``dsa_kl`` belongs to the indexer's loss, not to
  the attention). Layer: kernels.
* ``moe.load_max_over_mean`` and ``dsa.selected_pairs_pct``: the program's
  gauges ``hvd_moe_load_max_over_mean`` (largest layer; the held and absent
  assignments beside it give the MFU's assignments per token) and
  ``hvd_dsa_selected_pairs`` over ``hvd_dsa_causal_pairs`` (all layers).
  The selection's counts are the set-up forward's (they depend on the
  lengths alone); the routing load is stamped anew here, after the window,
  from the parameters the last step left (``Family.stamp_routing``: mean
  over the pool's batches).

Where the program has no such scope, kernel or gauge, that metric is left
out."""

import os
import re

from lib import after_window, cell as cell_mod, flops_moe_dsa as flops, \
    spans as sp, trace as tr

SCOPES = {"dsa.indexer_ms": "attn.indexer", "dsa.select_ms": "attn.select",
          "dsa.attend_ms": "attn.sparse",
          "dsa.indexer_loss_ms": "attn.indexer_loss",
          "moe.route_ms": "moe.route", "moe.experts_ms": "moe.experts"}


def in_scope(framework_name: str, scope: str) -> bool:
    """``scope`` as a whole component of the op's framework name, bare or
    wrapped by a transformation: ``jvp(attn.sparse)``,
    ``transpose(jvp(attn.sparse))``."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)",
                     framework_name) is not None


def by_scope(ops, names, steps):
    total = dict.fromkeys(SCOPES, 0.0)
    for name, start, end in ops:
        text = names.get(name, "")
        for metric, scope in SCOPES.items():
            if in_scope(text, scope):
                total[metric] += end - start
    return {m: ns / 1e6 / steps for m, ns in total.items() if ns}


def gauges():
    """{name: {layer: value}} of the program's routing and selection
    gauges that have been stamped."""
    try:
        from horovod_tpu.obs.registry import parse_exposition, registry
        samples = parse_exposition(registry().render())
    except (ImportError, AttributeError):
        return {}
    out = {}
    for (name, labels), value in samples.items():
        if name.startswith(("hvd_moe_", "hvd_dsa_")):
            out.setdefault(name, {})[dict(labels).get("layer")] = value
    return out


def read(trace, run, cell):
    config = cell["config"]
    if config["family"] != "lm_moe_dsa":
        return {}
    out = {}
    for hook in after_window.HOOKS:
        hook()
    found = gauges()
    load = found.get("hvd_moe_load_max_over_mean")
    if load:
        out["moe.load_max_over_mean"] = max(load.values())
    chosen = found.get("hvd_dsa_selected_pairs")
    among = found.get("hvd_dsa_causal_pairs")
    if chosen and among:
        out["dsa.selected_pairs_pct"] = 100.0 * sum(chosen.values()) \
            / sum(among.values())

    held = found.get("hvd_moe_held_assignments")
    absent = found.get("hvd_moe_absent_assignments")
    per_token = None
    if held and absent:
        per_token = config["num_experts_per_tok"] * sum(held.values()) / (
            sum(held.values()) + sum(absent.values()))
    tokens_per_s = run["steps"] * run["units_per_step"] / run["window_s"]
    need = flops.lm_moe_dsa_train_flop_per_token(config, run["seq_len"],
                                                 per_token)
    out["mfu_pct.lm_moe_dsa"] = 100.0 * tokens_per_s * need / (
        cell["chips"] * cell["peaks"]["bf16_flop_per_s"])

    plane = trace.devices[0]
    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    out.update(by_scope(tr.leaf_ops(plane), sp.framework_names(xplane),
                        run["steps"]))
    kernel_ns = tr.matching_ns(
        plane, lambda name: tr.short_name(name).startswith(
            ("dsa_fwd", "dsa_bwd")))
    if kernel_ns:
        args = (config, run["batch_per_chip"], run["seq_len"])
        least = max(flops.dsa_attend_flop_per_step(*args)
                    / cell["peaks"]["bf16_flop_per_s"],
                    flops.dsa_attend_bytes_per_step(*args)
                    / cell["peaks"]["hbm_bytes_per_s"])
        out["dsa_attend_roofline"] = 100.0 * least / (
            kernel_ns / 1e9 / run["steps"])
    return out
