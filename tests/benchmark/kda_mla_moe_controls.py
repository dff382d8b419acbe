"""Controls for family ``lm_kda_mla_moe``'s reference check: wrong blocks and
a lower precision, each put in the program's place, which the check must
call not correct. A control replaces attributes of the program's modules
(the mixers and the check look ``gated_delta_rule``, ``flash_attention``,
``_rms_norm`` and ``kda_tile.tile_bwd`` up when they are traced) or fields
of the family's ``TransformerConfig`` (``reference_check(state, cfg=)``);
the reference, the weights and the limits stay the cell's.

``tests/benchmark/test_bench_kda_mla_moe.py`` runs every control at the toy
size on the CPU. On the chip, from the root of a checkout,

    python3 tests/benchmark/kda_mla_moe_controls.py SEED[,SEED..] [NAME,..]

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
REAL_CELL = "kimi_kda_train_8k_1chip"


def controls(cfg):
    """name -> (attributes to replace [(module, name, value)], fields of
    the ``TransformerConfig`` to replace)."""
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.ops import gated_delta, kda_tile, pallas_attention
    from horovod_tpu.parallel import transformer
    rule, attend = gated_delta.gated_delta_rule, \
        pallas_attention.flash_attention
    norm, tile_bwd = transformer._rms_norm, kda_tile.tile_bwd
    d_nope = cfg.mla.d_nope

    def fp8(x):
        # float8_e4m3's three bits of mantissa; the exponent keeps its
        # range, as a scaled fp8 tensor's would (q's entries, about 1/128,
        # are below e4m3's least normal number). Not ``astype`` there and
        # back: XLA may drop that as excess precision, and on the chip
        # did for the rule's operands.
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)

    def no_shared(q, k, v, **kw):
        return attend(q, k.at[..., d_nope:].set(0), v, **kw)

    def shared_without_gradient(q, k, v, **kw):
        # The forward is the block's; dq and dk stop at the first 128.
        q, k = (jnp.concatenate([x[..., :d_nope],
                                 lax.stop_gradient(x[..., d_nope:])],
                                axis=-1) for x in (q, k))
        return attend(q, k, v, **kw)

    def dg_per_head(*args):
        # The forward is the block's; dg as a gate per head would have it.
        dq, dk, dv, dg, dbeta = tile_bwd(*args)
        return dq, dk, dv, jnp.broadcast_to(
            jnp.mean(dg, axis=-1, keepdims=True), dg.shape), dbeta

    return {
        "no_decay": ([(gated_delta, "gated_delta_rule",
                       lambda q, k, v, g, beta, **kw:
                       rule(q, k, v, g * 0, beta, **kw))], {}),
        # The scalar gate: each head's decay as its mean over the channels.
        "decay_per_head": ([(gated_delta, "gated_delta_rule",
                             lambda q, k, v, g, beta, **kw:
                             rule(q, k, v, g.mean(-1), beta, **kw))], {}),
        "no_shared_key_part": ([(pallas_attention, "flash_attention",
                                 no_shared)], {}),
        "softmax_scores": ([], {"moe_score": "softmax"}),
        "no_scaling_factor": ([], {"moe_scale": 1.0}),
        "wrong_norm_eps": ([], {"norm_eps": 1e-2}),
        "dg_per_head": ([(kda_tile, "tile_bwd", dg_per_head)], {}),
        "shared_key_part_without_gradient": (
            [(pallas_attention, "flash_attention",
              shared_without_gradient)], {}),
        # The nearest precision below bf16: every norm's output, and the
        # mixers' operands, through float8_e4m3.
        "fp8_norm_outputs": ([(transformer, "_rms_norm",
                               lambda x, *a, **kw: fp8(norm(x, *a, **kw)))],
                             {}),
        "fp8_mixer_operands": ([
            (gated_delta, "gated_delta_rule", lambda q, k, v, g, beta, **kw:
             rule(fp8(q), fp8(k), fp8(v), g, beta, **kw)),
            (pallas_attention, "flash_attention", lambda q, k, v, **kw:
             attend(fp8(q), fp8(k), fp8(v), **kw))], {}),
    }


@contextlib.contextmanager
def in_place(family, name):
    """The family's ``cfg`` for ``reference_check(state, cfg=)`` with the
    control ``name`` in the program's place (None: the block as it stands,
    cfg None)."""
    import jax
    if name is None:
        yield None
        return
    patches, fields = controls(family.cfg)[name]
    # The kernels' entries are jit-cached by shape: a tile function
    # replaced under them needs them traced again, now and afterwards.
    cached = any(attr == "tile_bwd" for _, attr, _ in patches)
    before = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    try:
        for mod, attr, value in patches:
            setattr(mod, attr, value)
        if cached:
            jax.clear_caches()
        yield dataclasses.replace(family.cfg, **fields)
    finally:
        for mod, attr, value in before:
            setattr(mod, attr, value)
        if cached:
            jax.clear_caches()


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
            [None, *controls(family.cfg)]
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
