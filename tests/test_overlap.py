"""Collective/compute overlap, pinned on the REAL TPU compiler.

The transformer family's data-parallel step (``make_parallel_train_step``
on a dp = 4 mesh, the ``lm_dp4_4chip`` cell's program at half its depth) is
compiled for a described ``v5e:2x2`` (compile-only:
``jax.experimental.topologies`` needs the TPU compiler plugin but NO
devices) with the program's own start-up arguments
(``utils/chips.enable_async_collectives`` merged them into
``LIBTPU_INIT_ARGS`` when ``horovod_tpu`` was imported, before libtpu was
loaded). The compiled schedule must hold what the overlap rests on:

* the layers' gradient all-reduces as ``async-collective-start`` ...
  ``async-collective-done`` pairs with the backward's own fusions between
  them, AHEAD of the last ``flash_bwd`` call, so the wire runs under the
  backward and not behind it;
* every asynchronous all-reduce with ONE operand: the compiler's combiner
  merges independent all-reduces into a variadic one, and this libtpu
  leaves a variadic all-reduce synchronous. The plan's barrier chain
  (``ops/fusion.reduce_in_backward``) is what keeps them apart.

Findings of PR 31's compiles, recorded so nobody re-chases them (``PERF.md``
section 6 has the chip's numbers): ``--xla_enable_async_all_reduce`` and
``--xla_tpu_enable_async_collective_fusion_fuse_all_reduce`` are both off
by default and neither acts alone; with both on, a collective still goes
nowhere unless its result is DUE inside the backward (a data dependency of
the backward on it: ``optimization_barrier`` with the activation
cotangent), because the scheduler puts a done next to its consumer and the
optimizer is the only consumer otherwise; an ``optimization_barrier`` whose
one half feeds only another barrier loses that half, so the tie and the
chain share one barrier; a constant threaded through barriers is forwarded
and ties nothing. The all-reduce combiner still has no option that a
program can set for a step somebody else compiles, so buckets under about
120 MB that are independent are merged whatever the fusion threshold says.

A compile is not a run: what the schedule is worth in milliseconds is the
``lm_dp4_4chip`` cell against ``lm_step_1chip`` (``PERF.md`` section 2).
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from jax.sharding import NamedSharding, PartitionSpec as P

LAYERS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plugin in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.slow
def test_tpu_schedule_runs_the_layers_all_reduces_under_the_backward(
        topo, monkeypatch):
    # slow: a whole-step compile at real widths, about a minute.
    import horovod_tpu  # noqa: F401  (sets the start-up arguments)
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    from horovod_tpu.parallel.transformer import (TransformerConfig,
                                                  make_parallel_train_step)
    from horovod_tpu.utils.chips import ASYNC_ALLREDUCE_ARGS
    import os
    assert set(ASYNC_ALLREDUCE_ARGS) <= set(
        os.environ["LIBTPU_INIT_ARGS"].split())
    # Code that asks the backend sees the CPU here and would put the flash
    # kernels into the interpreter.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()

    cfg = TransformerConfig(vocab=50304, d_model=2048, n_heads=16,
                            n_layers=LAYERS, d_ff=8192, dtype=jnp.bfloat16,
                            attn_backend="pallas",
                            unembed_dtype=jnp.bfloat16)
    mesh = create_hybrid_mesh(devices=list(topo.devices), dp=4)
    init_state, step = make_parallel_train_step(
        cfg, mesh, optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(init_state, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((32, 2048), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", None)))
    with jax.enable_x64(False):      # Mosaic has no f64; the chip runs none
        text = jax.jit(step, donate_argnums=(0, 1)).lower(
            *state, tok, tok).compile().as_text()
    jax.clear_caches()

    lines = text.splitlines()
    body = lines[next(i for i, l in enumerate(lines)
                      if l.startswith("ENTRY")):]

    def at(pattern):
        return [i for i, l in enumerate(body) if re.search(pattern, l)]

    flash_bwd = at(r"custom-call\(.*flash_bwd")
    starts = at(r"^\s*%async-collective-start\S* = ")
    dones = at(r"^\s*%async-collective-done\S* = ")
    assert len(flash_bwd) >= LAYERS and len(starts) == len(dones)
    # The wire runs under the backward: pairs that END before the last
    # flash_bwd call, for the buckets of all layers but the lowest two
    # (a bucket is in flight under the layer below its own).
    ahead = [d for d in dones if d < flash_bwd[-1]]
    assert len(ahead) >= 2 * (LAYERS - 2), (dones, flash_bwd)
    # A pair has the backward's fusions between its start and its done.
    for s, d in list(zip(starts, dones))[:len(ahead)]:
        assert any("fusion(" in l or "custom-call(" in l
                   for l in body[s + 1:d]), (s, d)
    # Asynchronous means one operand; no gradient bucket was merged into a
    # variadic all-reduce inside the backward.
    variadic = [l for l in body[:flash_bwd[-1]]
                if re.search(r"= \([^=]*\) all-reduce\(", l)]
    assert not variadic, variadic[:2]
    # Every layer's bucket keeps its scope wherever it was issued.
    for k in range(LAYERS):
        assert f"optimizer/allreduce.bucket{k}/psum" in text
