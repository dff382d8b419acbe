"""Controls for family ``lm_swa_moe``'s reference check: wrong blocks and a
lower precision, each put in the program's place, which the check must call
not correct. A control replaces attributes of the program's modules (the
mixer and the check look ``flash_attention``, ``_output_gate`` and
``_rms_norm`` up when they are traced) or fields of the family's
``TransformerConfig`` (``reference_check(state, cfg=)``); the reference, the
weights (and their published layout) and the limits stay the cell's.

``tests/benchmark/test_bench_swa_moe.py`` runs every control at the toy size
on the CPU. On the chip, from the root of a checkout,

    python3 tests/benchmark/swa_moe_controls.py SEED[,SEED..] [NAME,..]

prints the real cell's ``reference_check`` line (``ok`` and every reading
beside its limit) for the block as it stands and for each control: the
readings PERF.md section 6 quotes and the family's limits were set from.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL_CELL = "trinity_swa_train_8k_1chip"


def controls(cfg, seq_len: int):
    """name -> (attributes to replace [(module, name, value)], fields of
    the ``TransformerConfig`` to replace)."""
    from jax import lax
    from horovod_tpu.ops import pallas_attention
    from horovod_tpu.parallel import transformer
    attend, norm = pallas_attention.flash_attention, transformer._rms_norm
    w = cfg.swa

    def fp8(x):
        # float8_e4m3's three bits of mantissa, the exponent's range kept
        # (``kda_mla_moe_controls.py`` says why not ``astype``).
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)

    tile = pallas_attention._blocks(seq_len, w.window)[0]
    return {
        # Full causal attention on the window layers.
        "no_window": ([], {"swa": dataclasses.replace(w, window=seq_len)}),
        "window_one_tile_wider": (
            [], {"swa": dataclasses.replace(w, window=w.window + tile)}),
        "rope_on_full": ([], {"rope_theta": w.rope_theta}),
        "no_gate": ([(transformer, "_output_gate", lambda o, gate: o)], {}),
        "no_post_norms": ([], {"post_norms": False}),
        # The nearest precision below bf16: the attention's operands, and
        # every norm's output.
        "fp8_operands": ([(pallas_attention, "flash_attention",
                           lambda q, k, v, **kw:
                           attend(fp8(q), fp8(k), fp8(v), **kw))], {}),
        "fp8_norm_outputs": ([(transformer, "_rms_norm",
                               lambda x, *a, **kw: fp8(norm(x, *a, **kw)))],
                             {}),
    }


@contextlib.contextmanager
def in_place(family, name):
    """The family's ``cfg`` for ``reference_check(state, cfg=)`` with the
    control ``name`` in the program's place (None: the block as it stands,
    cfg None)."""
    if name is None:
        yield None
        return
    patches, fields = controls(family.cfg, family.seq_len)[name]
    before = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    try:
        for mod, attr, value in patches:
            setattr(mod, attr, value)
        yield dataclasses.replace(family.cfg, **fields)
    finally:
        for mod, attr, value in before:
            setattr(mod, attr, value)


def main(argv):
    sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]
    from horovod_tpu.utils.chips import enable_compile_cache
    enable_compile_cache()
    import jax
    import horovod_tpu as hvd
    from lib.cell import Context
    from run import load_module, named, read_json
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = named(bench["workloads"], REAL_CELL, "workload")
    config = read_json(os.path.join(
        ROOT, named(bench["configs"], cell["config"], "config")["file"]))
    traffic = read_json(os.path.join(ROOT, "benchmarks", "traffic",
                                     cell["traffic"] + ".json"))
    for seed in (int(s) for s in argv[0].split(",")):
        ctx = Context(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=0, trace=False, rehearse=False,
                      devices=jax.devices()[:1])
        hvd.init(devices=ctx.devices)
        family = load_module("families", config["family"]).build(ctx)
        state = family.init()
        names = argv[1].split(",") if len(argv) > 1 else \
            [None, *controls(family.cfg, family.seq_len)]
        for name in names:
            name = None if name in (None, "as_it_stands") else name
            print(json.dumps({"control": name or "as_it_stands",
                              "seed": seed}), flush=True)
            try:
                with in_place(family, name) as cfg:
                    family.reference_check(state, cfg=cfg)
            except Exception as e:  # noqa: BLE001 - a reading, not a run
                print(json.dumps({"control": name, "seed": seed,
                                  "error": repr(e)[:500]}), flush=True)
        del state
        hvd.shutdown()


if __name__ == "__main__":
    main(sys.argv[1:])
