"""``gdn.scan_ms`` by stage, for family ``lm_gdn_moe`` (nothing for another
family): of device 0's leaf ops, forward and backward, whose framework name
carries the program's scope ``gdn.scan``,

* ``gdn.scan_walk_ms``: those whose instruction is named ``gdn_fwd*`` or
  ``gdn_bwd*``, the Pallas kernels of the state's walk over the chunks
  (``ops/pallas_gated_delta.py``; ``pallas_call(name=)``);
* ``gdn.scan_local_ms``: every other one: the chunk-local stage (A, the
  solve, W, U, Aqk and their transposes: the kernels ``gdn_local_fwd`` /
  ``gdn_local_bwd`` or, where the program has none, XLA's einsums), the L2
  norms of q and k, the gates, pads and reshapes around the rule. On the
  XLA backend, whose walk is a ``lax.scan``, the walk is in here too.

Both over the steps traced; their sum is ``gdn.scan_ms``
(``layer_metrics/lm_gdn_moe.py``). Layer: linear attention. Where no op
carries the scope (a CPU's trace, a program without it) both are left
out."""

import os

from layer_metrics.lm_moe_dsa import in_scope
from lib import cell as cell_mod, spans as sp, trace as tr

WALK = ("gdn_fwd", "gdn_bwd")


def by_stage(ops, names, steps):
    """{metric: ms a step} of the leaf ops ``(name, start, end)`` under the
    scope ``gdn.scan`` (``names``: instruction -> framework name)."""
    total = {}
    for name, start, end in ops:
        if in_scope(names.get(name, ""), "gdn.scan"):
            stage = "walk" if tr.short_name(name).startswith(WALK) \
                else "local"
            total[stage] = total.get(stage, 0.0) + end - start
    if not total:
        return {}
    return {f"gdn.scan_{stage}_ms": total.get(stage, 0.0) / 1e6 / steps
            for stage in ("walk", "local")}


def read(trace, run, cell):
    if cell["config"]["family"] != "lm_gdn_moe":
        return {}
    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    return by_stage(tr.leaf_ops(trace.devices[0]),
                    sp.framework_names(xplane), run["steps"])
