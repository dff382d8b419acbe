#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the training main path runs on a
TPU chip, through the entry points a user would call.

    python chip_smoke.py             # one chip, three phases (below)
    python chip_smoke.py --chips 4   # the across-chips path only (below)
    python chip_smoke.py --rehearse  # tiny sizes on the CPU; NOT a pass

One chip (default), each phase at a full published width, a few steps:

* ``train_resnet50`` — ``models.resnet50(1000, bf16)``, 224x224, batch 128:
  ``create_train_state`` -> ``DistributedOptimizer`` -> ``make_train_step``
  driven by ``Trainer.fit`` (which stages input with
  ``data.prefetch_to_device``), then ``save_checkpoint`` and a verified
  ``restore_checkpoint``. Finite, falling loss; bit-identical restore.
* ``train_lm`` — the 470M transformer LM at the bench width (vocab 32768,
  d_model 2048, 16 heads, 8 layers, d_ff 8192, seq 2048, batch 8, bf16,
  ``attn_backend="pallas"``) through ``make_parallel_train_step``. The
  compiled step must contain the Pallas kernel (``tpu_custom_call``), the
  compiled kernels must agree with plain XLA attention on a small input
  (forward and gradient), and the loss must be finite and falling. Ends
  with ``save_sharded``.
* ``serve_lm`` — that checkpoint through ``serve.restore_for_inference``
  into a ``GenerationEngine`` that answers a few ``generate`` requests;
  the restored weights' prefill logits must agree with the training
  forward, and the paged decode kernel with its reference.

Four chips (``--chips 4``; run by hand, the driver never passes it):

* ``tpurun_np4`` — FIRST, while this parent has not touched a backend: the
  native core built from source, then ``tpurun -np 4``: one process per
  chip, env-world, a short training worker; every rank on its own chip,
  params bit-identical across ranks at the end.
* ``single_controller`` — ``hvd.init()`` over the four chips: eager
  ``allreduce/allgather/broadcast`` across them, the LM dp step (full
  width, depth cut) with a fused all-reduce and the Pallas kernel in the
  compiled HLO, every replica/shard on its own device, and loss + param
  checksum after k steps compared with the same global batch on one chip.

The LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Per-phase compile seconds, run seconds, losses and ``peak_bytes_in_use`` go
on earlier lines. Any failing phase ends the run non-zero with
``"ok": false``. With no accelerator the script exits non-zero naming the
platform it found and prints no result line. ``--rehearse`` (never a
default) runs the same code at toy sizes on whatever backend is there,
says so on every line, and exits 3 with ``"ok": false``.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# One process uses the chip: everything below runs in THIS process, except
# `tpurun -np 4`, which this process launches before it touches a backend.
from horovod_tpu.utils.chips import enable_compile_cache  # noqa: E402

CACHE_DIR = enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

# Full (published) sizes vs the explicit CPU rehearsal's toy sizes.
FULL = dict(
    resnet=dict(image=224, batch=128, classes=1000, epochs=4),
    lm=dict(vocab=32768, d_model=2048, n_heads=16, n_layers=8, d_ff=8192,
            seq=2048, batch=8, steps=4),
    lm4=dict(n_layers=2, steps=3),          # --chips 4: depth cut only
    # T=2048: four 512-tiles a head, so the compiled check runs plain
    # pairs, diagonal pairs and the never-visited pairs above them.
    kernel_check=dict(B=1, T=2048, H=2, D=128),
    serve=dict(max_slots=4, max_len=256, prompt=100, new_tokens=16,
               requests=3),
    paged=dict(S=4, H=16, d=128, bs=16, n_blocks=64, nb=8),
)
TOY = dict(
    resnet=dict(image=32, batch=8, classes=10, epochs=3),
    lm=dict(vocab=512, d_model=256, n_heads=2, n_layers=2, d_ff=512,
            seq=128, batch=2, steps=3),
    lm4=dict(n_layers=1, steps=2, batch=4),
    kernel_check=dict(B=1, T=128, H=2, D=128),
    serve=dict(max_slots=2, max_len=32, prompt=12, new_tokens=4,
               requests=2),
    paged=dict(S=2, H=2, d=128, bs=8, n_blocks=16, nb=4),
)

_cache_events = {"hits": 0, "misses": 0}


def _count_cache_event(name, **_):
    if name.endswith("/cache_hits"):
        _cache_events["hits"] += 1
    elif name.endswith("/cache_misses"):
        _cache_events["misses"] += 1


jax.monitoring.register_event_listener(_count_cache_event)


def _emit(**fields):
    print(json.dumps(fields), flush=True)


def _peak_bytes():
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _free():
    """Drop dead device buffers between phases (the phases share 16 GB)."""
    gc.collect()


def _finite_and_falling(losses, what):
    assert all(np.isfinite(l) for l in losses), f"{what}: non-finite {losses}"
    assert losses[-1] < losses[0], f"{what}: loss did not fall: {losses}"


# ---------------------------------------------------------------------------
# Phase 1: ResNet-50 through the core flax stack + Trainer + checkpoint.
# ---------------------------------------------------------------------------

def phase_train_resnet50(size, tmp):
    from horovod_tpu import callbacks, models, trainer, training
    c = size["resnet"]
    model = models.resnet50(num_classes=c["classes"], dtype=jnp.bfloat16)
    shape = (c["batch"], c["image"], c["image"], 3)
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros(shape, jnp.float32),
        optax.sgd(0.02, momentum=0.9))
    step = training.make_train_step(model, dist_opt)

    rng = np.random.RandomState(0)
    batches = [(rng.standard_normal(shape).astype(np.float32),
                rng.randint(0, c["classes"], size=(c["batch"],)))
               for _ in range(2)]

    class _Clock(callbacks.Callback):
        """Wall time of every batch; the first one carries the compile."""
        def __init__(self):
            self.t, self.dts = None, []

        def on_batch_begin(self, batch):
            self.t = time.perf_counter()

        def on_batch_end(self, batch, logs=None):
            self.dts.append(time.perf_counter() - self.t)

    clock = _Clock()
    tr = trainer.Trainer(step, state, verbose=False)
    t0 = time.perf_counter()
    history = tr.fit(lambda: iter(batches), epochs=c["epochs"],
                     callbacks=[clock])
    jax.block_until_ready(tr.state.params)
    total = time.perf_counter() - t0
    losses = [h["loss"] for h in history]
    _finite_and_falling(losses, "train_resnet50")

    # Checkpoint and a VERIFIED restore (manifest CRCs), bit-identical.
    t1 = time.perf_counter()
    path = trainer.save_checkpoint(os.path.join(tmp, "resnet"), tr.state)
    restored = trainer.restore_checkpoint(os.path.join(tmp, "resnet"),
                                          tr.state, verify=True)
    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        tr.state.params, restored.params))
    assert same, "restored ResNet-50 params differ from the saved state"
    assert int(restored.step) == int(tr.state.step) == 2 * c["epochs"]
    _emit(phase="train_resnet50", ok=True, model="resnet50",
          image=c["image"], batch=c["batch"], classes=c["classes"],
          steps=len(clock.dts), epoch_mean_losses=losses,
          compile_s=round(clock.dts[0], 2),
          run_s=round(total - clock.dts[0], 2),
          checkpoint_s=round(time.perf_counter() - t1, 2),
          checkpoint=os.path.basename(path), restore_bit_identical=True,
          peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------------------
# Phase 2: the transformer LM through make_parallel_train_step.
# ---------------------------------------------------------------------------

def _lm_cfg(c, **over):
    from horovod_tpu.parallel.transformer import TransformerConfig
    kw = dict(vocab=c["vocab"], d_model=c["d_model"], n_heads=c["n_heads"],
              n_layers=c["n_layers"], d_ff=c["d_ff"], dtype=jnp.bfloat16,
              attn_backend="pallas", unembed_dtype=jnp.bfloat16)
    kw.update(over)
    return TransformerConfig(**kw)


def _lm_batch(c, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(0)
    sh = NamedSharding(mesh, P("dp", None))
    tok = rng.randint(0, c["vocab"], size=(c["batch"], c["seq"]))
    lab = rng.randint(0, c["vocab"], size=(c["batch"], c["seq"]))
    return (jax.device_put(tok.astype(np.int32), sh),
            jax.device_put(lab.astype(np.int32), sh))


def _lm_train(c, mesh, steps):
    """Build, AOT-compile and run the LM step; returns the pieces the
    phases check. The carried state is donated (as the benchmark's LM
    family does): an undonated 470M f32 + Adam state would sit in HBM twice."""
    from horovod_tpu.parallel.transformer import make_parallel_train_step
    cfg = _lm_cfg(c)
    init_state, step = make_parallel_train_step(
        cfg, mesh, optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1))
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tokens, labels = _lm_batch(c, mesh)

    def update(p, o, tok, lab):     # (jit copies step's own .lower otherwise)
        return step(p, o, tok, lab)

    t0 = time.perf_counter()
    compiled = jax.jit(update, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens, labels).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    losses = []
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = compiled(params, opt_state, tokens,
                                           labels)
        losses.append(float(loss))
    run_s = time.perf_counter() - t0
    return dict(cfg=cfg, params=params, opt_state=opt_state, hlo=hlo,
                losses=losses, compile_s=compile_s, run_s=run_s)


def _check_flash_vs_xla(c):
    """The COMPILED flash kernels (forward and fused backward) against
    plain XLA attention on a small input — interpret mode cannot show
    that the chip computes the same numbers. At the full size every kind
    of tile pair of the causal schedule occurs (plain, diagonal, dead)."""
    from horovod_tpu.ops import pallas_attention as pa
    B, T, H, D = c["B"], c["T"], c["H"], c["D"]
    qkv = jax.random.normal(jax.random.PRNGKey(1), (B, T, H * 3 * D),
                            jnp.float32).astype(jnp.bfloat16)

    def ref(x):
        r = x.reshape(B, T, H, 3, D)
        o = pa._xla_attention(r[..., 0, :], r[..., 1, :], r[..., 2, :],
                              True, float(D) ** -0.5)
        return o.reshape(B, T, H * D)

    def loss(fn):
        return lambda x: jnp.sum(fn(x).astype(jnp.float32) ** 2)

    kern = lambda x: pa.flash_attention_qkv(x, H, causal=True)  # noqa: E731
    o_k, o_r = jax.jit(kern)(qkv), jax.jit(ref)(qkv)
    g_k, g_r = jax.jit(jax.grad(loss(kern)))(qkv), \
        jax.jit(jax.grad(loss(ref)))(qkv)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))

    fwd, bwd = rel(o_k, o_r), rel(g_k, g_r)
    assert fwd < 3e-2 and bwd < 3e-2, (fwd, bwd)
    return {"fwd_rel_err": round(fwd, 5), "bwd_rel_err": round(bwd, 5)}


def phase_train_lm(size, tmp, on_tpu):
    from jax.sharding import Mesh
    from horovod_tpu.parallel.checkpoint import save_sharded
    c = size["lm"]
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    kernel_check = _check_flash_vs_xla(size["kernel_check"])
    out = _lm_train(c, mesh, c["steps"])
    n_kernels = out["hlo"].count("tpu_custom_call")
    if on_tpu:
        assert n_kernels > 0, \
            "no tpu_custom_call in the compiled LM step: the Pallas " \
            "kernel did not run compiled"
    _finite_and_falling(out["losses"], "train_lm")
    t0 = time.perf_counter()
    ckpt = os.path.join(tmp, "lm")
    save_sharded(ckpt, c["steps"], out["params"], out["opt_state"])
    _emit(phase="train_lm", ok=True,
          **{k: c[k] for k in ("vocab", "d_model", "n_heads", "n_layers",
                               "d_ff", "seq", "batch")},
          attn_backend="pallas", tpu_custom_calls=n_kernels,
          flash_vs_xla=kernel_check, losses=out["losses"],
          compile_s=round(out["compile_s"], 2),
          run_s=round(out["run_s"], 2),
          checkpoint_s=round(time.perf_counter() - t0, 2),
          peak_bytes_in_use=_peak_bytes())
    return out["cfg"], ckpt


# ---------------------------------------------------------------------------
# Phase 3: train -> serve handoff.
# ---------------------------------------------------------------------------

def _check_paged_kernel(c):
    from horovod_tpu.ops.pallas_paged_attention import (
        paged_attention_reference, paged_decode_attention)
    S, H, d, bs, n_blocks, nb = (c[k] for k in
                                 ("S", "H", "d", "bs", "n_blocks", "nb"))
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (S, H, d), jnp.float32).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_blocks, bs, H, d),
                           jnp.float32).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_blocks, bs, H, d),
                           jnp.float32).astype(jnp.bfloat16)
    rng = np.random.RandomState(3)
    tbl = rng.permutation(n_blocks - 1)[:S * nb].reshape(S, nb) + 1
    pos = rng.randint(0, nb * bs, size=(S,))
    pos[-1] = -1                                     # one inactive slot
    tbl, pos = jnp.asarray(tbl, jnp.int32), jnp.asarray(pos, jnp.int32)
    out = np.asarray(paged_decode_attention(q, kp, vp, tbl, pos),
                     np.float32)
    ref = np.asarray(paged_attention_reference(q, kp, vp, tbl, pos),
                     np.float32)
    err = float(np.max(np.abs(out - ref)))
    assert np.all(np.isfinite(out)) and err < 3e-2, err
    return {"max_abs_err": round(err, 5)}


def phase_serve_lm(size, cfg, ckpt):
    from jax.sharding import Mesh
    from horovod_tpu import serve
    from horovod_tpu.parallel.transformer import (forward, init_kv_cache,
                                                  prefill)
    c = size["serve"]
    t0 = time.perf_counter()
    params = serve.restore_for_inference(ckpt, dtype="bf16")["params"]
    restore_s = time.perf_counter() - t0
    assert params["embed"].dtype == jnp.bfloat16
    params = jax.device_put(params)

    # The restored weights compute what the training forward computes.
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, cfg.vocab, size=(c["prompt"],)).astype(np.int32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    n_ref = min(c["max_len"], 128)
    ref_toks = rng.randint(0, cfg.vocab, size=(n_ref,)).astype(np.int32)
    ref = np.asarray(jax.jit(
        lambda p, t: forward(p, t[None], cfg, mesh)[0][0])(params, ref_toks))
    cache = init_kv_cache(cfg, max_slots=1, max_len=c["max_len"])
    _, plog = jax.jit(lambda p, t, k: prefill(p, t, k, 0, cfg))(
        params, ref_toks, cache)
    plog = np.asarray(plog)
    assert plog.shape == ref.shape == (n_ref, cfg.vocab)
    assert np.all(np.isfinite(plog))
    diff = float(np.max(np.abs(plog - ref)) / (np.max(np.abs(ref)) + 1e-9))
    assert diff < 0.1, f"prefill vs forward logits differ: rel {diff}"
    del cache

    eng = serve.GenerationEngine(
        params, cfg, serve.GenerationConfig(
            max_slots=c["max_slots"], max_len=c["max_len"],
            default_max_new_tokens=c["new_tokens"]))
    try:
        t0 = time.perf_counter()
        eng.warmup()
        warmup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        handles = [eng.submit([int(t) for t in prompt[:c["prompt"] - i]])
                   for i in range(c["requests"])]
        results = [h.result(300) for h in handles]
        again = eng.generate([int(t) for t in prompt], timeout=300)
        run_s = time.perf_counter() - t0
    finally:
        eng.shutdown()
    for r in results + [again]:
        assert r["n_tokens"] == c["new_tokens"], r
        assert all(0 <= t < cfg.vocab for t in r["tokens"]), r
    # The engine's first greedy token is the training forward's argmax at
    # the prompt's last position (within bf16 noise of the maximum).
    last = np.asarray(jax.jit(
        lambda p, t: forward(p, t[None], cfg, mesh)[0][0, -1])(
            params, prompt))
    first = results[0]["tokens"][0]
    gap = float(np.max(last) - last[first])
    assert gap <= 0.02 * float(np.max(np.abs(last))), (first, gap)
    # Greedy streams are deterministic: same prompt, same tokens.
    assert again["tokens"] == results[0]["tokens"], (again, results[0])
    _emit(phase="serve_lm", ok=True, restore_dtype="bf16",
          restore_s=round(restore_s, 2), warmup_compile_s=round(warmup_s, 2),
          run_s=round(run_s, 2), requests=len(results) + 1,
          tokens=[r["tokens"] for r in results],
          prefill_vs_forward_rel_err=round(diff, 5),
          first_token_logit_gap_to_forward_argmax=round(gap, 5),
          paged_kernel_vs_reference=_check_paged_kernel(size["paged"]),
          peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------------------
# --chips 4, part 1: tpurun -np 4 (one process per chip, env-world).
# ---------------------------------------------------------------------------

def tpurun_worker():
    """One rank of `tpurun -np 4`: the launcher pinned one chip to this
    process; train a few env-world steps over the host plane and report."""
    from horovod_tpu import models, training
    hvd.init()
    w = hvd.world()
    assert w.env_world, "worker must run under tpurun (env-world)"
    r, s = hvd.rank(), hvd.size()
    local = jax.local_devices()
    # Every rank holds its chip when this returns: a rendezvous of all.
    held = hvd.allreduce(jnp.full((4,), float(r + 1)), average=False,
                         name="hold")
    assert np.allclose(np.asarray(held), sum(range(1, s + 1))), held
    g = hvd.allgather(jnp.full((1, 2), float(r)), name="g")
    assert np.allclose(np.asarray(g)[:, 0], np.arange(s)), g
    b = hvd.broadcast(jnp.asarray([float(r), 2.0]), root_rank=0, name="b")
    assert np.allclose(np.asarray(b), [0.0, 2.0]), b

    model = models.MnistCNN()
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(r),   # divergent seeds: sync must fix it
        jnp.zeros((2, 784)), optax.sgd(0.01))
    state = hvd.broadcast_parameters(state, root_rank=0)
    step = training.make_train_step(model, dist_opt)
    rng = np.random.RandomState(7)      # same seed = same global batch
    x = rng.randn(8 * s, 784).astype(np.float32)
    y = np.argmax(x @ rng.randn(784, 10).astype(np.float32), axis=1)
    losses = []
    for _ in range(6):
        state, m = step(state, training.shard_batch(
            (jnp.asarray(x), jnp.asarray(y))))
        losses.append(float(np.asarray(m["loss"])))
    _finite_and_falling(losses, f"rank {r}")
    crc = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        crc = zlib.crc32(np.asarray(leaf).tobytes(), crc)
    crcs = hvd.allgather_object(crc)
    assert len(set(crcs)) == 1, f"params differ across ranks: {crcs}"
    # One os.write: four ranks share the pipe and a line must not tear.
    os.write(1, ("RANK_REPORT " + json.dumps({
        "rank": r, "size": s, "pid": os.getpid(),
        "visible_chip": os.environ.get("TPU_VISIBLE_CHIPS"),
        "platform": local[0].platform, "kind": local[0].device_kind,
        "n_local_devices": len(local), "losses": losses,
        "params_crc32": crc}) + "\n").encode())
    hvd.shutdown()


def phase_tpurun_np4(rehearse):
    from horovod_tpu.utils.compat import backend_initialized
    assert not backend_initialized(), \
        "the tpurun parent must not have touched a jax backend"
    # Built from what git would commit: the native core from source, here.
    coord = os.path.join(ROOT, "horovod_tpu", "coord")
    t0 = time.perf_counter()
    # (clean only: with the binary gone, the first rank's coord client
    # rebuilds it under its build lock.)
    subprocess.run(["make", "-C", coord, "clean"], check=True,
                   capture_output=True, text=True)
    build_s = time.perf_counter() - t0
    cmd = [sys.executable, "-m", "horovod_tpu.launcher", "-np", "4"]
    if rehearse:
        cmd.append("--cpu")
    cmd += [sys.executable, os.path.abspath(__file__), "--tpurun-worker"]
    t0 = time.perf_counter()
    env = dict(os.environ, PYTHONPATH=ROOT)
    if rehearse:
        env["XLA_FLAGS"] = ""
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    run_s = time.perf_counter() - t0
    if p.returncode != 0:
        sys.stderr.write(p.stdout[-4000:] + "\n" + p.stderr[-8000:] + "\n")
        raise RuntimeError(f"tpurun -np 4 exited {p.returncode}")
    reports = sorted((json.loads(l[len("RANK_REPORT "):])
                      for l in p.stdout.splitlines()
                      if l.startswith("RANK_REPORT ")),
                     key=lambda d: d["rank"])
    assert [d["rank"] for d in reports] == [0, 1, 2, 3], reports
    assert all(d["n_local_devices"] == 1 for d in reports), reports
    assert len({d["params_crc32"] for d in reports}) == 1, reports
    assert len({d["pid"] for d in reports}) == 4, reports
    if not rehearse:
        assert all(d["platform"] == "tpu" for d in reports), reports
        chips = [d["visible_chip"] for d in reports]
        assert sorted(chips) == ["0", "1", "2", "3"], \
            f"ranks did not each get their own chip: {chips}"
    assert os.path.exists(os.path.join(coord, "libhvdcoord.so"))
    _emit(phase="tpurun_np4", ok=True, native_core="built from source",
          clean_s=round(build_s, 2), run_s=round(run_s, 2),
          ranks=[{k: d[k] for k in ("rank", "visible_chip", "platform",
                                    "kind", "n_local_devices",
                                    "params_crc32")} for d in reports],
          losses_rank0=reports[0]["losses"],
          params_bit_identical_across_ranks=True)


# ---------------------------------------------------------------------------
# --chips 4, part 2: single controller over the four chips.
# ---------------------------------------------------------------------------

def _devices_of(x):
    return {s.device for s in x.addressable_shards}


def phase_single_controller(size):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    hvd.init()
    n = hvd.size()
    assert n == 4 == len(jax.devices()), (n, jax.devices())

    # Eager collectives across the four chips, per-rank inputs.
    stacked = lambda a: jax.device_put(  # noqa: E731
        a, NamedSharding(hvd.mesh(), P(hvd.AXIS)))
    x = np.arange(n * 6, dtype=np.float32).reshape(n, 6)
    xs = stacked(x)
    assert len(_devices_of(xs)) == 4, _devices_of(xs)
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(xs, average=False)), x.sum(0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(xs, average=True)), x.mean(0), rtol=1e-6)
    g = np.asarray(hvd.allgather(stacked(
        np.stack([np.full((2, 3), r, np.float32) for r in range(n)]))))
    assert g.shape == (2 * n, 3) and np.array_equal(
        g[:, 0], np.repeat(np.arange(n, dtype=np.float32), 2)), g
    for root in (0, n - 1):
        np.testing.assert_array_equal(
            np.asarray(hvd.broadcast(xs, root_rank=root)), x[root])

    # The LM dp step over four chips vs the same global batch on one.
    c = dict(size["lm"], **size["lm4"])
    mesh4 = create_hybrid_mesh(dp=4)
    out4 = _lm_train(c, mesh4, c["steps"])
    leaf = out4["params"]["embed"]
    assert len(_devices_of(leaf)) == 4, \
        f"param replicas sit on {_devices_of(leaf)}"
    tok4, _ = _lm_batch(c, mesh4)
    assert len(_devices_of(tok4)) == 4 and \
        tok4.addressable_shards[0].data.shape[0] == c["batch"] // 4
    n_ar = out4["hlo"].count("all-reduce")
    assert n_ar > 0, "no all-reduce in the compiled 4-chip step"
    n_k = out4["hlo"].count("tpu_custom_call")
    assert n_k > 0 or jax.devices()[0].platform != "tpu"

    def checksum(params):
        return float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                         for l in jax.tree_util.tree_leaves(params)))

    sum4 = checksum(out4["params"])
    losses4, compile4, run4 = out4["losses"], out4["compile_s"], \
        out4["run_s"]
    del out4, leaf, tok4
    _free()
    out1 = _lm_train(c, Mesh(np.array(jax.devices()[:1]), ("dp",)),
                     c["steps"])
    sum1 = checksum(out1["params"])
    _finite_and_falling(losses4, "4-chip dp")
    np.testing.assert_allclose(losses4, out1["losses"], rtol=2e-2)
    np.testing.assert_allclose(sum4, sum1, rtol=1e-3)
    _emit(phase="single_controller", ok=True, chips=4,
          eager=["allreduce", "allgather", "broadcast"],
          lm={k: c[k] for k in ("vocab", "d_model", "n_heads", "n_layers",
                                "d_ff", "seq", "batch")},
          all_reduce_ops_in_hlo=n_ar, tpu_custom_calls=n_k,
          devices_holding_params=4, devices_holding_batch=4,
          losses_4chip=losses4, losses_1chip=out1["losses"],
          param_abs_sum_4chip=sum4, param_abs_sum_1chip=sum1,
          compile_s_4chip=round(compile4, 2), run_s_4chip=round(run4, 2),
          compile_s_1chip=round(out1["compile_s"], 2),
          peak_bytes_in_use=_peak_bytes())
    hvd.shutdown()


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default): the three one-chip phases; 4: the "
                         "across-chips path and what it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend is there (the CPU "
                         "here); never a pass: ok=false, exit code 3")
    ap.add_argument("--tpurun-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.tpurun_worker:
        tpurun_worker()
        return 0

    size = TOY if args.rehearse else FULL
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    phase = "start"
    device = None
    try:
        if args.chips == 4:
            # Before this process touches a backend (it would hold all
            # four chips and every rank would fail on a chip's lock).
            phase = "tpurun_np4"
            phase_tpurun_np4(args.rehearse)
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if not args.rehearse and device["platform"] != "tpu":
            sys.stderr.write(
                f"chip_smoke: needs a TPU and found platform "
                f"{device['platform']!r} ({device['kind']}, "
                f"{device['count']} device(s)); nothing was run. "
                f"--rehearse runs toy sizes here and is not a pass.\n")
            return 2
        if device["count"] != args.chips:
            sys.stderr.write(
                f"chip_smoke: --chips {args.chips} needs exactly that many "
                f"devices and jax reports {device['count']}\n")
            return 2
        _emit(phase="start", rehearsal=bool(args.rehearse), device=device,
              compile_cache_dir=CACHE_DIR,
              device_nodes=sorted(glob.glob("/dev/accel*")
                                  + glob.glob("/dev/vfio/*")))
        if args.chips == 4:
            phase = "single_controller"
            phase_single_controller(size)
        else:
            hvd.init()
            phase = "train_resnet50"
            phase_train_resnet50(size, tmp)
            _free()
            phase = "train_lm"
            cfg, ckpt = phase_train_lm(size, tmp,
                                       on_tpu=device["platform"] == "tpu")
            _free()
            phase = "serve_lm"
            phase_serve_lm(size, cfg, ckpt)
            hvd.shutdown()
    except BaseException:  # noqa: BLE001 — reported, then exit non-zero
        traceback.print_exc()
        _emit(phase=phase, ok=False)
        print(json.dumps({"ok": False, "failed_phase": phase,
                          "device": device}), flush=True)
        return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    _emit(phase="done", compile_cache=dict(_cache_events, dir=CACHE_DIR))
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "phases passed at toy "
                          "sizes; NOT a chip run", "device": device}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
