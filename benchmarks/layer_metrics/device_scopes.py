"""The device step split by the program's named scopes, and the flash
kernels by their names.

``device_step.forward_ms``, ``.backward_ms``, ``.optimizer_ms`` and
``.unscoped_ms``: device 0's leaf ops summed by the scope in the op's
framework name (``jvp(forward)``, ``transpose(jvp(forward))``,
``optimizer``; ``training.make_train_step`` and
``parallel/transformer._vag`` set them), over the steps traced. A fusion
carries the scope of its root instruction, so the split is by root;
``unscoped`` is what no scope claims (copies and asynchronous ``-done`` ops
the compiler added, which carry no framework name). The four add up to the
summed leaf ops, which is ``device_step_ms.<family>`` where ops do not
overlap.

``flash_attn.fwd_ms_per_step`` and ``.bwd_ms_per_step``: the kernels of
``ops/pallas_attention.py`` by their ``pallas_call(name=)``, which the
compiler makes the instruction's name: ``flash_fwd`` and ``flash_bwd*``
(the fused backward, or the split ``_dq`` and ``_dkv``).

The framework name is not in the event (``lib/spans.framework_names``
reads it from the trace file's event metadata). Where no op carries a scope
or a kernel's name (a program from before them), nothing is reported.
Layers: step builders, kernels."""

import os

from lib import cell as cell_mod, spans as sp, trace as tr


def split(ops, names, steps):
    """``ops``: leaf ops ``(name, start, end)``; ``names``: event name ->
    framework name. Returns the metrics (possibly none)."""
    by_scope = dict.fromkeys((sp.FORWARD, sp.BACKWARD, sp.OPTIMIZER,
                              sp.UNSCOPED), 0.0)
    fwd = bwd = 0.0
    for name, start, end in ops:
        by_scope[sp.scope_of(names.get(name, ""))] += end - start
        kernel = tr.short_name(name)       # flash_fwd.3, flash_bwd_dq.1
        if kernel.startswith("flash_fwd"):
            fwd += end - start
        elif kernel.startswith("flash_bwd"):
            bwd += end - start
    out = {}
    if by_scope[sp.FORWARD] or by_scope[sp.BACKWARD] \
            or by_scope[sp.OPTIMIZER]:
        out = {f"device_step.{k}_ms": ns / 1e6 / steps
               for k, ns in by_scope.items()}
    if fwd or bwd:
        out["flash_attn.fwd_ms_per_step"] = fwd / 1e6 / steps
        out["flash_attn.bwd_ms_per_step"] = bwd / 1e6 / steps
    return out


def read(trace, run, cell):
    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    return split(tr.leaf_ops(trace.devices[0]),
                 sp.framework_names(xplane), run["steps"])
