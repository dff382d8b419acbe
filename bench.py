"""Benchmark: synthetic training throughput (ResNet-50 + transformer LM).

Mirrors the reference's benchmark methodology — `tf_cnn_benchmarks.py
--variable_update horovod` with synthetic data (``docs/benchmarks.md:8-98``)
— on the flagship north-star workload (ResNet-50,
``examples/keras_imagenet_resnet50.py``) plus a transformer-LM training
step (the TPU-era matmul-dominated workload: bf16, Pallas flash attention,
``parallel/transformer.py``). The baseline for ``vs_baseline`` is the
reference's only published absolute throughput: ResNet-101 at 1656.82
images/sec across 16 Pascal GPUs = 103.55 images/sec/GPU
(``docs/benchmarks.md:24-54``; see /root/repo/BASELINE.md); other models'
baselines are FLOPs-scaled from it so the ratio compares hardware.

Default: prints TWO JSON lines {"metric", "value", "unit", "vs_baseline"}
— ResNet-50 images/sec/chip first (the primary metric), then the
transformer-LM tokens/sec/chip with TFLOP/s and MFU. Every line names the
device it ran on (``platform``, ``device_kind``, ``device_count``). A
measurement needs a chip: without one ``bench.py`` exits non-zero naming
the platform it found. ``HVD_BENCH_SMOKE=1`` (what ``ci.sh`` passes) is
the one explicit way to the toy CPU configuration, and its lines say
``"smoke": true``.
``--scaling`` (single-controller only): measures throughput at world sizes
1, 2, 4, ... and the full device count, printing one scaling-efficiency
JSON line per size (rate_N / (N · rate_1) — the reference's headline
metric: 90% @ 128 GPUs; north star ≥90% @ v5e-64) followed by the standard
full-world images/sec/chip line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from horovod_tpu.utils.chips import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import models, training

# Reference baseline: 1656.82 images/sec on 16 GPUs running ResNet-101
# (docs/benchmarks.md:24-54) — the reference's only absolute throughput.
# For other models the per-GPU baseline is FLOPs-scaled from it (the
# reference GPU's estimated rate on that model), so vs_baseline stays an
# apples-to-apples hardware ratio rather than crediting cheaper models.
BASELINE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16


def _median_rate(run_once, state, units_per_round, rounds):
    """Median-of-rounds throughput: time ``rounds`` independent regions and
    take the median rate. A single timed region is exposed to one-off
    host hiccups — the median of several short regions is robust to any
    single glitch while keeping dispatches async *within* each region."""
    rates = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        state, loss = run_once(state)
        # End every timed region with a host transfer of the last loss:
        # it cannot return before the region's work is done.
        final_loss = float(loss)
        dt = time.perf_counter() - t0
        assert np.isfinite(final_loss), final_loss
        rates.append(units_per_round / dt)
    return sorted(rates)[len(rates) // 2], state


def _baseline_for(model: str) -> float:
    return BASELINE_IMG_PER_SEC_PER_DEVICE * (
        _FWD_GMACS["resnet101"] / _FWD_GMACS[model])

# Analytic FLOPs model: forward GMACs per image × 2 (multiply-accumulate =
# 2 FLOPs — the convention XLA's own cost analysis uses; its estimate for
# the ResNet-50 train step, 23.9 GFLOP/img, matches this model) × 3
# (backward ≈ 2× forward). Lets the JSON line report TFLOP/s and MFU so the
# number is judgeable against the chip's peak, not just a 2017 GPU.
_FWD_GMACS = {"resnet50": 4.09, "resnet101": 7.80, "vgg16": 15.47,
              "inception3": 5.73, "cifar20": 0.041}
TRAIN_GFLOP_PER_IMAGE = {k: 3 * 2 * v for k, v in _FWD_GMACS.items()}

# Peak dense bf16 TFLOP/s per chip — the denominators for MFU — keyed by
# the EXACT ``jax.devices()[0].device_kind`` string. Only kinds a run of
# this repo has reported are listed; a device that is not here is an
# error, never a guessed peak.
_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197.0,
}


def _smoke() -> bool:
    """The one explicit way off the chip: ``HVD_BENCH_SMOKE=1`` (ci.sh)."""
    return bool(int(os.environ.get("HVD_BENCH_SMOKE", "0")))


def _device_fields() -> dict:
    """Where a line was measured; stamped on every JSON line."""
    devs = jax.devices()
    fields = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind,
              "device_count": len(devs)}
    if _smoke():
        fields["smoke"] = True
    return fields


def _require_chip() -> None:
    """A measurement path that finds no chip fails — it never falls back
    to a toy model under the same metric name."""
    if _smoke():
        return
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU and found platform "
            f"{platform!r} ({jax.devices()[0].device_kind}); the toy CPU "
            f"configuration runs only under HVD_BENCH_SMOKE=1")


def _peak_tflops_per_chip():
    """Peak of the running device; None only in smoke mode (a CPU has no
    MFU). An unknown TPU kind is an error."""
    if _smoke():
        return None
    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_TFLOPS:
        raise SystemExit(
            f"no peak TFLOP/s on record for device_kind {kind!r}; add it "
            f"to bench._PEAK_TFLOPS with its source")
    return _PEAK_TFLOPS[kind]


def _peak_bytes_per_chip():
    """Per-chip peak HBM bytes from the runtime's allocator stats, or None
    where the backend keeps none (CPU). Read AFTER the measured region so
    the number covers the train step — it is how the ZeRO memory win
    (opt state ÷ world size) shows up in BENCH_*.json."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — stats are best-effort telemetry
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


# Per-model TPU configs (the reference benchmark family, tf_cnn_benchmarks
# --model {resnet50, resnet101, vgg16, inception3}; docs/benchmarks.md:5-6).
_TPU_CONFIGS = {
    "resnet50": dict(model="resnet50", image=224, batch_per_chip=128,
                     warmup=5, iters=4, classes=1000, steps_per_call=8, rounds=3),
    "resnet101": dict(model="resnet101", image=224, batch_per_chip=96,
                      warmup=5, iters=4, classes=1000, steps_per_call=8, rounds=3),
    # VGG has no BN: classic SGD needs the small-lr recipe or it blows up.
    "vgg16": dict(model="vgg16", image=224, batch_per_chip=96,
                  warmup=5, iters=4, classes=1000, steps_per_call=8,
                  rounds=3, lr=0.01),
    "inception3": dict(model="inception3", image=299, batch_per_chip=96,
                       warmup=5, iters=4, classes=1000, steps_per_call=8, rounds=3),
}


def _bench_config(model: str = "resnet50"):
    _require_chip()
    if _smoke():
        # No scan in smoke mode: compiling the scanned step on the virtual
        # CPU mesh costs minutes and there is no dispatch overhead to
        # amortize.
        return dict(model="cifar20", image=64, batch_per_chip=16,
                    warmup=2, iters=5, classes=10, steps_per_call=1)
    # steps_per_call: lax.scan over k steps inside one dispatch — amortizes
    # the per-call host->device dispatch overhead exactly like
    # tf_cnn_benchmarks' in-graph loop over synthetic data.
    return dict(_TPU_CONFIGS[model])


def _build_model(cfg):
    """Benchmark models use local (per-replica) BatchNorm — the reference /
    Goyal configuration; cross-replica BN is opt-in via axis_name."""
    name = cfg["model"]
    # The HVD_FUSED_PARTS sweep (docs/benchmarks.md r5) enters here, at
    # model CONSTRUCTION — as a module attribute it keys the jit cache
    # and is uniform across ranks, which a trace-time env read was not.
    fused_parts = tuple(os.environ.get(
        "HVD_FUSED_PARTS", "reduce,expand,shortcut").split(","))
    if name == "resnet50":
        return models.resnet50(num_classes=cfg["classes"],
                               dtype=jnp.bfloat16,
                               conv_backend=cfg.get("conv_backend", "xla"),
                               fused_parts=fused_parts)
    if name == "resnet101":
        return models.resnet101(num_classes=cfg["classes"],
                                dtype=jnp.bfloat16,
                                conv_backend=cfg.get("conv_backend", "xla"),
                                fused_parts=fused_parts)
    if name == "vgg16":
        return models.vgg16(num_classes=cfg["classes"], dtype=jnp.bfloat16)
    if name == "inception3":
        return models.inception_v3(num_classes=cfg["classes"],
                                   dtype=jnp.bfloat16)
    return models.cifar_resnet_v1(20, dtype=jnp.float32)


def _measure_phases(model, dist_opt, cfg, state, data, accum, rate):
    """Per-phase wall attribution (ISSUE 6 satellite): three compiled
    probes over the same sharded batch — backward only; backward + the
    gradient exchange (the same fused all-reduce or reduce-scatter/
    all-gather round the step takes, same wire/overlap knobs); and the
    full step (derived from the measured rate). The exchange's EXPOSED
    wall time is ``t(exchange) - t(backward)``: when overlap hides the
    collectives behind backward compute it collapses toward zero even
    though the same bytes move — which is exactly what BENCH_r06 needs to
    show, not just img/s. Single-controller only (the env-world exchange
    is host-plane and already measured by its wait times)."""
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.ops import fusion as _f

    if hvd.world().env_world:
        return None
    mesh = hvd.mesh()
    vag = training._build_value_and_grad(
        model, training.cross_entropy_loss, False)
    zero = bool(cfg.get("zero", False))
    wire = cfg.get("wire_dtype")
    overlap = bool(cfg.get("overlap", False))
    rng0 = jax.random.PRNGKey(0)

    def _grads(state, x, y):
        if accum == 1:
            _, g = vag(state.params, state.batch_stats, x, y, rng0)
        else:
            _, _, g, _ = training._accumulate_grads(
                vag, state.params, state.batch_stats, x, y,
                lambda i: jax.random.fold_in(rng0, i), accum, None)
        return g

    def _consume(tree):
        # Sum every inexact leaf: keeps the whole backward (or exchange)
        # live through DCE while returning one scalar to fetch.
        tot = jnp.zeros((), jnp.float32)
        for leaf in jax.tree_util.tree_leaves(tree):
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                tot = tot + jnp.sum(leaf.astype(jnp.float32))
        return jax.lax.pmean(tot, hvd.AXIS)

    def _bwd_only(state, x, y):
        return _consume(_grads(state, x, y))

    def _bwd_exchange(state, x, y):
        g = _grads(state, x, y)
        if zero:
            plan = state.opt_state.plan
            emit = tuple(range(len(plan.buckets))) if overlap else None
            shards = _f.fused_reduce_scatter(
                g, plan, average=True, wire_dtype=wire, emit_order=emit)
            return _consume(_f.fused_allgather_params(shards, plan))
        return _consume(_f.fused_allreduce(
            g, average=True, wire_dtype=wire, overlap=overlap))

    def _sharded(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(), P(hvd.AXIS), P(hvd.AXIS)),
            out_specs=P(), check_vma=False))

    times = {}
    for name, fn in (("backward", _sharded(_bwd_only)),
                     ("exchange", _sharded(_bwd_exchange))):
        fn(state, *data).block_until_ready()  # compile + warm
        reps = []
        for _ in range(max(3, int(cfg.get("iters", 3)))):
            t0 = time.perf_counter()
            fn(state, *data).block_until_ready()
            reps.append(time.perf_counter() - t0)
        times[name] = sorted(reps)[len(reps) // 2]

    rows = jax.tree_util.tree_leaves(data)[0].shape[0]
    t_step = rows / rate  # wall per optimizer step, from the headline rate
    t_bwd = times["backward"]
    t_coll = max(0.0, times["exchange"] - t_bwd)
    t_upd = max(0.0, t_step - times["exchange"])
    share = (lambda t: round(min(1.0, t / t_step), 3)) if t_step > 0 \
        else (lambda t: 0.0)
    return {
        "backward_s": round(t_bwd, 6),
        "collective_exposed_s": round(t_coll, 6),
        "update_s": round(t_upd, 6),
        "backward_share": share(t_bwd),
        "collective_share": share(t_coll),
        "update_share": share(t_upd),
    }


def measure(devices=None, cfg=None, want_phases: bool = False):
    """Images/sec of the compiled distributed train step over ``devices``
    (default: all). Returns total (not per-chip) throughput — or
    ``(rate, phases)`` with ``want_phases=True`` (phases is None on
    env-world runs)."""
    cfg = cfg or _bench_config()
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.init(devices=devices)
    n = hvd.size()
    batch = cfg["batch_per_chip"] * n
    image, classes = cfg["image"], cfg["classes"]

    model = _build_model(cfg)

    x_shape = (batch, image, image, 3)
    # Init from a per-chip-sized sample: flax init runs a real forward pass
    # on one device, so a global-batch sample would OOM at pod scale.
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(0),
        jnp.zeros((cfg["batch_per_chip"],) + x_shape[1:], jnp.float32),
        optax.sgd(cfg.get("lr", 0.1), momentum=0.9),
        zero=bool(cfg.get("zero", False)),
        wire_dtype=cfg.get("wire_dtype"))
    accum = int(cfg.get("accum_steps", 1))
    if cfg["batch_per_chip"] % accum:
        raise SystemExit(
            f"--accum-steps {accum} does not divide the per-chip batch "
            f"of {cfg['batch_per_chip']}")
    step = training.make_train_step(
        model, dist_opt, accum_steps=accum,
        overlap=True if cfg.get("overlap") else None)

    # Materialize only local shards (a host-side global batch would be
    # multiple GB at pod scale).
    if hvd.world().env_world:
        # Independent process per chip: build just this rank's slice (the
        # shard_batch split), not the global batch — otherwise every rank
        # trains on all N shards and throughput is over-reported N×.
        r = hvd.rank()
        rng = np.random.RandomState(r)
        local = (cfg["batch_per_chip"],) + x_shape[1:]
        data = (
            jnp.asarray(rng.standard_normal(local).astype(np.float32)),
            jnp.asarray(rng.randint(0, classes,
                                    size=(cfg["batch_per_chip"],))),
        )
        for _ in range(cfg["warmup"]):
            state, metrics = step(state, data)
        float(metrics["loss"])

        def _region(s):
            for _ in range(cfg["iters"]):
                s, m = step(s, data)
            return s, m["loss"]

        rate, _ = _median_rate(_region, state, batch * cfg["iters"],
                               int(cfg.get("rounds", 1)))
        return (rate, None) if want_phases else rate

    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(hvd.mesh(), P(hvd.AXIS))

    def _shard_data(idx):
        rng = np.random.RandomState(hash(str(idx)) % 2**31)
        shape = tuple(s.stop - s.start if s.start is not None else dim
                      for s, dim in zip(idx, x_shape))
        return rng.standard_normal(shape).astype(np.float32)

    def _shard_labels(idx):
        rng = np.random.RandomState(1 + hash(str(idx)) % 2**31)
        rows = idx[0].stop - idx[0].start if idx[0].start is not None \
            else batch
        return rng.randint(0, classes, size=(rows,))

    data = (
        jax.make_array_from_callback(x_shape, sharding, _shard_data),
        jax.make_array_from_callback((batch,), sharding, _shard_labels),
    )

    k = int(cfg.get("steps_per_call", 1))
    if k > 1:
        def _body(s, _):
            s2, m = step(s, data)
            return s2, m["loss"]

        import functools

        # Donate the carried state: the inner step's donation is ignored
        # when traced under this jit, and an undonated TrainState copy
        # (~1 GB for VGG-16) would sit in HBM for the whole dispatch.
        @functools.partial(jax.jit, donate_argnums=0)
        def _multi(s):
            s2, losses = jax.lax.scan(_body, s, None, length=k)
            return s2, losses[-1]

        def run_once(s):
            s2, loss = _multi(s)
            return s2, loss
    else:
        def run_once(s):
            s2, m = step(s, data)
            return s2, m["loss"]

    for _ in range(cfg["warmup"]):
        state, loss = run_once(state)
    float(loss)  # full device->host sync before timing

    def _region(s):
        for _ in range(cfg["iters"]):
            s, loss = run_once(s)
        return s, loss

    rate, state = _median_rate(_region, state, batch * cfg["iters"] * k,
                               int(cfg.get("rounds", 1)))
    if not want_phases:
        return rate
    # Per-step rate (rows of one optimizer step / wall), for the phase
    # denominator — identical to `rate` since units_per_round counts rows.
    phases = _measure_phases(model, dist_opt, cfg, state, data, accum, rate)
    return rate, phases


# ---------------------------------------------------------------------------
# Transformer LM (the second BENCH metric): a matmul-dominated bf16 training
# step — Pallas flash attention, fused QKV, tied bf16 unembed — sized for one
# v5e chip. Where ResNet's MFU is bounded by XLA's conv kernels, this is the
# workload the MXU was built for; the analytic FLOPs model below counts
# matmul FLOPs only (2 per MAC, backward = 2x forward, causal attention at
# half), so MFU is not inflated by remat recompute or elementwise work.
# ---------------------------------------------------------------------------

_LM_TPU = dict(vocab=32768, d_model=2048, n_heads=16, n_layers=8,
               d_ff=8192, seq=2048, batch_per_chip=8,
               warmup=2, iters=6, steps_per_call=2, rounds=3)
_LM_SMOKE = dict(vocab=256, d_model=64, n_heads=2, n_layers=2,
                 d_ff=256, seq=128, batch_per_chip=4,
                 warmup=1, iters=2, steps_per_call=1)


def lm_train_gflop_per_token(c) -> float:
    """Matmul-only FLOPs: per layer fwd = 8·d² (qkv+proj) + 4·d·ff (ffn)
    + 2·T·d (causal QKᵀ+AV, halved) per token; + 2·d·V tied unembed;
    train = 3× forward."""
    d, ff, T, V, L = (c["d_model"], c["d_ff"], c["seq"], c["vocab"],
                      c["n_layers"])
    fwd = L * (8 * d * d + 4 * d * ff + 2 * T * d) + 2 * d * V
    return 3 * fwd / 1e9


def _lm_config():
    _require_chip()
    cfg = dict(_LM_SMOKE if _smoke() else _LM_TPU)
    # Experiment knob (docs/benchmarks.md LM experiments table): online
    # chunked cross-entropy instead of the dense [B,T,vocab] softmax.
    chunk = int(os.environ.get("HVD_LM_LOSS_CHUNK", "0"))
    if chunk:
        cfg["loss_chunk"] = chunk
    return cfg


def measure_lm(cfg=None) -> float:
    """Tokens/sec of the compiled transformer-LM train step over all
    visible devices — a pure dp mesh by default, dp×tp with
    ``cfg["tp"] > 1`` (the hybrid plane: Megatron-sharded weights, batch
    over dp; ISSUE 8), or the full 3-D dp×tp×pp mesh with
    ``cfg["pp"] > 1`` (the pipelined family: 1F1B schedule, gradient
    sync interpreted from the unified spec-grouped plan; ISSUE 20).
    Returns total (not per-chip) throughput. Single-controller only: the
    parallel transformer's mesh covers this process's devices, so an
    env-world run would train unsynced local replicas and report a
    meaningless rate."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    from horovod_tpu.parallel.transformer import (
        TransformerConfig, make_parallel_train_step)

    cfg = cfg or _lm_config()

    if hvd.is_initialized():
        hvd.shutdown()
    hvd.init()
    if hvd.world().env_world:
        raise SystemExit(
            "the transformer_lm benchmark is single-controller only (run "
            "without tpurun; one process drives all chips)")

    devs = jax.devices()
    n = len(devs)
    tp = int(cfg.get("tp", 1))
    pp = int(cfg.get("pp", 1))
    if tp < 1 or pp < 1 or n % (tp * pp):
        raise SystemExit(
            f"--tp {tp} × --pp {pp} must divide the visible device count "
            f"{n} (the mesh is dp={n}//(tp·pp) × tp × pp)")
    dp = n // (tp * pp)
    want_dp = cfg.get("mesh_dp")
    if want_dp is not None and int(want_dp) != dp:
        raise SystemExit(
            f"--mesh dp={want_dp},tp={tp},pp={pp} does not match the "
            f"visible device count {n} (needs dp×tp×pp == devices; dp "
            f"here is {dp})")
    mesh = create_hybrid_mesh(dp=dp, tp=tp, pp=pp)
    tcfg = TransformerConfig(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], dtype=jnp.bfloat16,
        attn_backend="xla" if _smoke() else "pallas",
        unembed_dtype=jnp.bfloat16, remat=bool(cfg.get("remat", False)),
        loss_chunk=int(cfg.get("loss_chunk", 0)))
    opt = optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    if pp > 1:
        from horovod_tpu.parallel.pp_transformer import (
            make_pp_transformer_train_step)
        if cfg["n_layers"] % pp:
            raise SystemExit(
                f"--pp {pp} must divide n_layers={cfg['n_layers']} (each "
                f"pipeline stage owns n_layers//pp layers)")
        # Accumulation is NATIVE in the pipelined family — microbatches
        # ARE the accumulation, one planned exchange per optimizer step —
        # so --accum-steps sets the microbatch count (min 2: a 1-deep
        # pipeline is all bubble).
        micro = max(2, int(cfg.get("accum_steps", 1)))
        if cfg["batch_per_chip"] % micro:
            raise SystemExit(
                f"batch_per_chip={cfg['batch_per_chip']} must divide into "
                f"--accum-steps {micro} microbatches for the pipelined "
                f"path")
        init_state, step = make_pp_transformer_train_step(
            tcfg, mesh, opt, n_microbatches=micro,
            wire_dtype=cfg.get("wire_dtype"),
            zero=bool(cfg.get("zero", False)),
            overlap=True if cfg.get("overlap") else None)
    else:
        init_state, step = make_parallel_train_step(
            tcfg, mesh, opt, wire_dtype=cfg.get("wire_dtype"),
            zero=bool(cfg.get("zero", False)),
            overlap=True if cfg.get("overlap") else None,
            accum_steps=int(cfg.get("accum_steps", 1)))
    params, opt_state = init_state(jax.random.PRNGKey(0))

    # tp ranks within a dp group replicate the same rows, so the global
    # batch scales with dp, not the chip count.
    B = cfg["batch_per_chip"] * dp
    T = cfg["seq"]
    rng = np.random.RandomState(0)
    sharding = NamedSharding(mesh, P("dp", None))
    tokens = jax.device_put(
        rng.randint(0, cfg["vocab"], size=(B, T)).astype(np.int32),
        sharding)
    labels = jax.device_put(
        rng.randint(0, cfg["vocab"], size=(B, T)).astype(np.int32),
        sharding)

    k = int(cfg.get("steps_per_call", 1))
    if k > 1:
        import functools

        def _body(carry, _):
            p2, o2, loss = step(*carry, tokens, labels)
            return (p2, o2), loss

        @functools.partial(jax.jit, donate_argnums=0)
        def _multi(carry):
            carry, losses = jax.lax.scan(_body, carry, None, length=k)
            return carry, losses[-1]

        def run_once(carry):
            return _multi(carry)
    else:
        def run_once(carry):
            p2, o2, loss = step(*carry, tokens, labels)
            return (p2, o2), loss

    carry = (params, opt_state)
    for _ in range(cfg["warmup"]):
        carry, loss = run_once(carry)
    float(loss)

    def _region(c):
        for _ in range(cfg["iters"]):
            c, loss = run_once(c)
        return c, loss

    rate, _ = _median_rate(_region, carry, B * T * cfg["iters"] * k,
                           int(cfg.get("rounds", 1)))
    return rate


def _mesh_desc(n: int, tp: int, pp: int = 1) -> str:
    dp = n // (max(1, tp) * max(1, pp))
    return (f"dp{dp}" + (f",tp{tp}" if tp > 1 else "")
            + (f",pp{pp}" if pp > 1 else ""))


def lm_line(wire_dtype=None, tp: int = 1, pp: int = 1, zero: bool = False,
            overlap: bool = False, accum_steps: int = 1,
            mesh_dp=None) -> dict:
    from horovod_tpu.ops.fusion import wire_dtype_name
    cfg = _lm_config()
    if wire_dtype:
        cfg["wire_dtype"] = wire_dtype
    cfg["tp"] = tp
    cfg["pp"] = pp
    cfg["zero"] = zero
    cfg["overlap"] = overlap
    cfg["accum_steps"] = accum_steps
    cfg["mesh_dp"] = mesh_dp
    rate = measure_lm(cfg)
    n = hvd.size()
    per_chip = rate / n
    gflop_tok = lm_train_gflop_per_token(cfg)
    # Hardware-ratio baseline, like the conv models: the reference GPU's
    # estimated tokens/sec at this FLOPs cost. With tp the per-chip FLOPs
    # fall by tp (the model is split), so the per-chip token rate is
    # still the apples-to-apples number.
    baseline = BASELINE_IMG_PER_SEC_PER_DEVICE * (
        TRAIN_GFLOP_PER_IMAGE["resnet101"] / gflop_tok)
    line = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(per_chip / baseline, 3),
        # per_chip = rate / ALL chips already spreads each token's FLOPs
        # over the tp split — no further /tp, or hybrid MFU reads tp×
        # low vs the tp=1 rows.
        "tflops_per_chip": round(per_chip * gflop_tok / 1e3, 1),
        # Knob provenance (ISSUEs 6+8): since the retarget onto the core
        # stack, the LM rides the same fused-bucket planes as the conv
        # family — every knob applies and is recorded.
        "accum_steps": int(accum_steps),
        "zero": bool(zero),
        "overlap": bool(overlap),
        "wire_dtype": wire_dtype_name(cfg.get("wire_dtype")),
        "tp": int(tp),
        "pp": int(pp),
        # The bench LM carries no experts; the field still appears so a
        # future MoE measurement is distinguishable from these lines.
        "ep": 1,
        "mesh": _mesh_desc(n, tp, pp),
        **_device_fields(),
    }
    # The hybrid HBM win (weights + opt state ÷ tp, opt state ÷ dp with
    # --zero) is only claimable if the line carries the number.
    peak_bytes = _peak_bytes_per_chip()
    if peak_bytes is not None:
        line["peak_bytes_per_chip"] = peak_bytes
    peak = _peak_tflops_per_chip()
    if peak:
        line["mfu"] = round(per_chip * gflop_tok / 1e3 / peak, 3)
    return line


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--scaling", action="store_true",
                   help="measure world sizes 1,2,4,... and report "
                        "scaling efficiency per size")
    p.add_argument("--model", default=None,
                   choices=sorted(_TPU_CONFIGS) + ["transformer_lm"],
                   help="benchmark model (default: resnet50 then "
                        "transformer_lm; the conv family mirrors the "
                        "reference's tf_cnn_benchmarks; ignored in "
                        "smoke mode)")
    p.add_argument("--conv-backend", default=None,
                   choices=["xla", "fused"],
                   help="ResNet conv backend: 'fused' routes the "
                        "bottleneck 1x1 convs through the fused Pallas "
                        "conv+BN+ReLU kernel (ops/pallas_conv.py)")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="in-step gradient accumulation: scan N microbatches "
                        "inside the compiled step, one fused allreduce per "
                        "accumulated step (docs/performance.md); the "
                        "per-chip batch is split, so the global batch per "
                        "optimizer update is unchanged")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1 sharded optimizer updates: fused "
                        "reduce-scatter + all-gather instead of the "
                        "all-reduce, optimizer state rank-sharded to "
                        "1/size per chip (docs/performance.md); recorded "
                        "in the JSON line alongside peak_bytes_per_chip "
                        "so the memory win is attributable")
    p.add_argument("--overlap", action="store_true",
                   help="backward-overlapped bucket collectives: per-"
                        "bucket gradient collectives issue in backward-"
                        "completion order behind optimization_barrier "
                        "pins so wire time hides behind backward compute "
                        "(docs/performance.md 'Overlap & wire formats'); "
                        "recorded in every JSON line")
    p.add_argument("--wire-dtype", default=None,
                   choices=["fp32", "bf16", "fp8"],
                   help="low-precision wire format for the gradient "
                        "collectives (fp32 scales, fp32 result "
                        "accumulation; fp8 is e4m3 with per-bucket "
                        "dynamic scaling); recorded in every JSON line")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel axis size for the hybrid dp×tp "
                        "mesh (transformer_lm only: Megatron-sharded "
                        "weights over tp, batch over dp=devices//tp; "
                        "docs/performance.md 'Hybrid dp×tp'); recorded "
                        "in every JSON line alongside 'mesh'")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel axis size for the 3-D dp×tp×pp "
                        "mesh (transformer_lm only: 1F1B schedule, stage-"
                        "owned weights, gradient sync from the unified "
                        "spec-grouped plan; docs/performance.md 'One "
                        "plan, every plane'); recorded in every JSON "
                        "line alongside 'mesh'")
    p.add_argument("--mesh", default=None,
                   help="explicit mesh spec 'dp=N,tp=M,pp=P' (must "
                        "multiply to the visible device count); "
                        "equivalent to --tp M --pp P with a dp sanity "
                        "check")
    args = p.parse_args()
    if args.accum_steps < 1:
        raise SystemExit(f"--accum-steps must be >= 1, got "
                         f"{args.accum_steps}")
    tp = args.tp
    pp = args.pp
    mesh_dp = None
    if args.mesh:
        import re as _re
        sizes = {}
        for part in args.mesh.split(","):
            m = _re.match(r"^\s*(dp|tp|pp)\s*=?\s*(\d+)\s*$", part)
            if not m:
                raise SystemExit(
                    f"--mesh expects 'dp=N,tp=M,pp=P' (got {part!r}); "
                    f"axes beyond dp/tp/pp are "
                    f"examples/transformer_lm.py territory")
            sizes[m.group(1)] = int(m.group(2))
        mtp = sizes.get("tp", 1)
        if tp != 1 and tp != mtp:
            raise SystemExit(
                f"--tp {tp} conflicts with --mesh {args.mesh!r}")
        tp = mtp
        mpp = sizes.get("pp", 1)
        if pp != 1 and pp != mpp:
            raise SystemExit(
                f"--pp {pp} conflicts with --mesh {args.mesh!r}")
        pp = mpp
        mesh_dp = sizes.get("dp")
    if tp < 1:
        raise SystemExit(f"--tp must be >= 1, got {tp}")
    if pp < 1:
        raise SystemExit(f"--pp must be >= 1, got {pp}")
    if args.model == "transformer_lm":
        if args.scaling:
            raise SystemExit(
                "--scaling is not supported for transformer_lm (the conv "
                "family's re-init-with-device-subsets machinery does not "
                "apply); run it without --scaling")
        print(json.dumps(lm_line(
            wire_dtype=args.wire_dtype, tp=tp, pp=pp,
            zero=bool(args.zero), overlap=bool(args.overlap),
            accum_steps=args.accum_steps, mesh_dp=mesh_dp)))
        return
    if tp > 1 or pp > 1:
        raise SystemExit(
            "--tp/--pp/--mesh beyond pure dp applies to --model "
            "transformer_lm (the hybrid and pipelined workloads): the "
            "conv family's flax models are neither tensor-sharded nor "
            "staged — a silent ignore would mislabel a pure-dp run as a "
            "multi-axis measurement")
    cfg = _bench_config(args.model or "resnet50")
    cfg["accum_steps"] = args.accum_steps
    cfg["zero"] = bool(args.zero)
    cfg["overlap"] = bool(args.overlap)
    if args.wire_dtype and args.wire_dtype != "fp32":
        cfg["wire_dtype"] = args.wire_dtype
    if args.conv_backend:
        if (args.model or "resnet50") not in ("resnet50", "resnet101"):
            raise SystemExit(
                "--conv-backend applies to the resnet models only (the "
                "fused kernel targets bottleneck 1x1 convs); a silent "
                "ignore would mislabel a stock run as a fused measurement")
        if cfg["model"] not in ("resnet50", "resnet101"):
            raise SystemExit(
                "--conv-backend has no effect in smoke mode (its config "
                "swaps the model to cifar20); run on TPU without "
                "HVD_BENCH_SMOKE for a fused measurement")
        cfg["conv_backend"] = args.conv_backend

    from horovod_tpu.ops.fusion import wire_dtype_name

    def _knob_fields():
        return {
            "accum_steps": int(cfg.get("accum_steps", 1)),
            "zero": bool(cfg.get("zero", False)),
            "overlap": bool(cfg.get("overlap", False)),
            "wire_dtype": wire_dtype_name(cfg.get("wire_dtype")),
            # The conv family is pure dp (flax models are neither
            # tensor-sharded nor staged); the fields still appear so
            # every JSON line is mesh-attributable.
            "tp": 1,
            "pp": 1,
            "mesh": _mesh_desc(hvd.size(), 1),
            **_device_fields(),
        }

    if args.scaling:
        # Scaling mode is single-controller only: it re-inits the world with
        # device subsets, which is ill-defined when other processes own part
        # of the mesh (jax.distributed) or in tpurun env-worlds.
        from horovod_tpu.utils import config as _hvd_config
        # Probe the ENV, not jax.process_count(): touching the backend here
        # would both defeat the check (count is 1 before distributed init)
        # and block a later jax.distributed initialization.
        if _hvd_config.launcher_size(1) > 1 \
                or os.environ.get("JAX_COORDINATOR_ADDRESS"):
            raise SystemExit(
                "--scaling requires a single-controller world (run without "
                "tpurun/jax.distributed; one process drives all chips)")
        devs = jax.devices()
        sizes = sorted({s for s in (2 ** p for p in range(8))
                        if s <= len(devs)} | {len(devs)})
        rate1 = None
        rate = None
        for n in sizes:
            rate = measure(devices=devs[:n], cfg=cfg)
            if n == 1:
                rate1 = rate
            eff = rate / (n * rate1) if rate1 else float("nan")
            print(json.dumps({
                "metric": f"{cfg['model']}_scaling_efficiency_{n}chips",
                "value": round(eff, 4),
                "unit": "fraction",
                "vs_baseline": round(eff / 0.90, 3),  # ref: 90% @ 128 GPUs
                "images_per_sec_total": round(rate, 2),
                **_knob_fields(),
            }))
        # Also emit the standard absolute metric (full world) so parsers
        # keyed on it always find it.
        per_chip = rate / len(devs)
        line = {
            "metric": f"{cfg['model']}_synthetic_images_per_sec_per_chip",
            "value": round(per_chip, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(per_chip / _baseline_for(cfg["model"]),
                                 3),
            **_knob_fields(),
        }
        peak_bytes = _peak_bytes_per_chip()
        if peak_bytes is not None:
            line["peak_bytes_per_chip"] = peak_bytes
        print(json.dumps(line))
        return

    rate, phases = measure(cfg=cfg, want_phases=True)
    per_chip = rate / hvd.size()
    line = {
        "metric": f"{cfg['model']}_synthetic_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / _baseline_for(cfg["model"]), 3),
        **_knob_fields(),
    }
    if phases is not None:
        line["phases"] = phases
    peak_bytes = _peak_bytes_per_chip()
    if peak_bytes is not None:
        line["peak_bytes_per_chip"] = peak_bytes
    tflops = per_chip * TRAIN_GFLOP_PER_IMAGE[cfg["model"]] / 1e3
    line["tflops_per_chip"] = round(tflops, 1)
    peak = _peak_tflops_per_chip()
    if peak:
        line["mfu"] = round(tflops / peak, 3)
    print(json.dumps(line), flush=True)

    if args.model is None:
        # Second BENCH metric: the transformer-LM step (matmul-dominated —
        # shows the framework sustains near-peak where the hardware allows).
        if hvd.world().env_world:
            print("skipping transformer_lm line: single-controller only",
                  file=sys.stderr)
        else:
            print(json.dumps(lm_line(wire_dtype=args.wire_dtype,
                                     zero=bool(args.zero),
                                     overlap=bool(args.overlap))),
                  flush=True)


if __name__ == "__main__":
    sys.exit(main())
