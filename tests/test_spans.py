"""Program spans and counters inside the training path (ISSUE 26): the
in-memory recorder of ``utils/timeline.py``, the spans ``Trainer.fit``,
``prefetch_to_device`` and the step wrappers record, the compile counter,
and the named scopes of the lowered step."""

import gc
import json
import os
import subprocess
import sys
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import callbacks, training
from horovod_tpu.data import prefetch_to_device
from horovod_tpu.obs.registry import registry
from horovod_tpu.trainer import Trainer
from horovod_tpu.utils import timeline as tl


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))


def _batches(n=4, rows=16, width=8, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(rows, width).astype(np.float32),
             rng.randint(0, 10, (rows,))) for _ in range(n)]


def _since(mark):
    """Spans recorded after ``mark = tl.spans()[-1].id`` (ids only grow)."""
    return [s for s in tl.spans() if s.id > mark]


def _mark():
    with tl.span("test.mark") as m:
        pass
    return m.id


# -- the recorder ------------------------------------------------------------

def test_nesting_parent_ids_annotate_and_drop():
    mark = _mark()
    with tl.span("outer", step=7) as outer:
        with tl.span("inner") as inner:
            tl.annotate(batch=3, queue_depth=2)
        with tl.span("gone") as gone:
            gone.drop()
    got = {s.name: s for s in _since(mark)}
    assert set(got) == {"outer", "inner"}
    assert got["outer"].parent == 0 and got["outer"].ids == {"step": 7}
    assert got["inner"].parent == got["outer"].id == outer.id
    assert got["inner"].ids == {"batch": 3, "queue_depth": 2}
    assert got["outer"].start_ns <= got["inner"].start_ns \
        <= got["inner"].end_ns <= got["outer"].end_ns
    assert inner.end_ns >= inner.start_ns          # readable after the with
    tl.annotate(ignored=True)                      # no open span: no-op


def test_a_raising_body_still_closes_its_span():
    mark = _mark()
    with pytest.raises(KeyError):
        with tl.span("raises"):
            raise KeyError("x")
    with tl.span("after"):
        pass
    got = {s.name: s for s in _since(mark)}
    assert got["after"].parent == 0, "the failed span left the stack dirty"
    assert "raises" in got


def test_two_threads_keep_their_own_stacks():
    mark = _mark()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with tl.span("worker.outer"):
            inside.set()
            release.wait(5)
            with tl.span("worker.inner"):
                pass

    t = threading.Thread(target=worker, name="spans-worker")
    t.start()
    inside.wait(5)
    with tl.span("main.while_worker_open"):
        pass
    release.set()
    t.join()
    got = {s.name: s for s in _since(mark)}
    assert got["main.while_worker_open"].parent == 0
    assert got["worker.inner"].parent == got["worker.outer"].id
    assert got["worker.outer"].thread != got["main.while_worker_open"].thread
    assert tl.thread_names()[got["worker.outer"].thread] == "spans-worker"


def test_ring_stays_bounded_and_keeps_the_newest():
    assert tl.RING_SPANS >= 65536
    gc.disable()             # a full collection would be a span among them
    try:
        for i in range(tl.RING_SPANS + 100):
            with tl.span("flood", i=i):
                pass
    finally:
        gc.enable()
    got = tl.spans()
    assert len(got) == tl.RING_SPANS
    assert got[-1].ids == {"i": tl.RING_SPANS + 99}
    assert all(s.name == "flood" for s in got)


def test_snapshot_is_on_the_wall_clock():
    before = time.time_ns()
    with tl.span("walled"):
        time.sleep(0.002)
    after = time.time_ns()
    s = tl.spans()[-1]
    slack = 1_000_000      # perf_counter and the wall clock drift a little
    assert before - slack <= s.start_ns <= s.end_ns <= after + slack
    assert s.end_ns - s.start_ns >= 2_000_000


def test_record_span_takes_perf_counter_readings():
    now = time.perf_counter_ns()
    wall = time.time_ns()
    tl.record_span("late.report", now - 5_000_000, now, what="x")
    s = tl.spans()[-1]
    assert (s.name, s.ids) == ("late.report", {"what": "x"})
    assert s.end_ns - s.start_ns == 5_000_000
    assert abs(s.end_ns - wall) < 1_000_000


def test_ring_survives_shutdown():
    hvd.init()
    with tl.span("before.shutdown", step=1):
        pass
    hvd.shutdown()
    assert tl.spans()[-1].name == "before.shutdown"
    hvd.init()


# -- the Timeline writes the same spans, on one clock --------------------------

def test_timeline_writes_origin_spans_and_maybe_op_once(tmp_path):
    path = tmp_path / "tl.json"
    t0 = time.time_ns()
    writer = tl.Timeline(str(path))
    with tl.span("fit.step", step=3):
        with tl.maybe_op(writer, "ckpt.write", tl.CKPT_WRITE):
            pass
    writer.close()
    with tl.maybe_op(None, "ckpt.write", tl.CKPT_WRITE):
        pass                       # no timeline: still a span in the ring
    assert [s.name for s in tl.spans()[-3:]] == \
        ["CKPT_WRITE", "fit.step", "CKPT_WRITE"]
    events = [e for e in json.load(open(path)) if e]
    origin = [e for e in events if e["name"] == "hvd_clock_origin"]
    assert len(origin) == 1
    assert abs(origin[0]["args"]["unix_ns"] - t0) < 1_000_000_000
    xs = [e for e in events if e["ph"] == "X"]
    assert [(e["name"], e["args"]) for e in xs if e["name"] == "fit.step"] \
        == [("fit.step", {"step": 3})]
    # Written as B/E on its own row by maybe_op, so not again as X.
    assert not [e for e in xs if e["name"] == "CKPT_WRITE"]
    assert [e["name"] for e in events if e["ph"] == "B"] == ["CKPT_WRITE"]
    rows = {e["args"]["name"] for e in events
            if e["name"] == "process_name"}
    assert {"program spans", "ckpt.write"} <= rows
    # B/E and X share the origin: the B of CKPT_WRITE lies inside fit.step.
    step = next(e for e in xs if e["name"] == "fit.step")
    begin = next(e for e in events if e["ph"] == "B")
    assert step["ts"] <= begin["ts"] <= step["ts"] + step["dur"] + 1


def test_timeline_drains_on_its_own_thread(tmp_path, monkeypatch):
    monkeypatch.setattr(tl.Timeline, "FLUSH_INTERVAL_SECS", 0.05)
    path = tmp_path / "tl.json"
    writer = tl.Timeline(str(path))
    with tl.span("drained.by.thread"):
        pass
    deadline = time.time() + 5
    while time.time() < deadline and not writer._span_threads:
        time.sleep(0.02)
    assert writer._span_threads, "the drain thread wrote nothing"
    writer.close()
    assert "drained.by.thread" in path.read_text()
    with tl.span("after.close"):       # a closed writer is no sink
        pass
    assert "after.close" not in path.read_text()


# -- the training path ---------------------------------------------------------

def _step_and_state():
    hvd.init()
    model = _MLP()
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 8)), optax.sgd(0.05))
    return training.make_train_step(model, dist_opt), state


def _fit(n=4, prefetch=2, cbs=None, step_and_state=None):
    step, state = step_and_state or _step_and_state()
    trainer = Trainer(step, state, verbose=False, prefetch=prefetch)
    mark = _mark()
    trainer.fit(lambda: _batches(n), epochs=1, callbacks=cbs)
    return _since(mark), trainer.state


def test_fit_and_prefetch_emit_joinable_spans():
    reg = registry()
    waits = reg.histogram("hvd_input_wait_seconds").count
    h2d = reg.counter("hvd_h2d_bytes_total").value
    got, _ = _fit(n=4, cbs=[callbacks.Callback()])
    by = {}
    for s in got:
        by.setdefault(s.name, []).append(s)
    steps = sorted(by["fit.step"], key=lambda s: s.start_ns)
    assert len(steps) == 4, "the turn that found the stream's end is dropped"
    first = steps[0].ids["step"]
    assert [s.ids["step"] for s in steps] == list(range(first, first + 4))
    for name, per_step in (("fit.next_batch", 1), ("fit.train_step", 1),
                           ("fit.callbacks", 2)):
        assert len(by[name]) == 4 * per_step, name
        assert {s.parent for s in by[name]} == {s.id for s in steps}
    # step.dispatch is the jitted call, inside fit.train_step.
    assert {s.parent for s in by["step.dispatch"]} == \
        {s.id for s in by["fit.train_step"]}
    # The input thread's spans join the loop's by batch number.
    loop, worker = steps[0].thread, by["H2D"][0].thread
    assert loop != worker
    assert {s.thread for s in by["input.source"]} == {worker}
    assert sorted(s.ids["batch"] for s in by["H2D"]) == [0, 1, 2, 3]
    assert sorted(s.ids["batch"] for s in by["input.source"]) == [0, 1, 2, 3]
    asked = sorted(by["fit.next_batch"], key=lambda s: s.start_ns)
    assert [s.ids["batch"] for s in asked] == [0, 1, 2, 3]
    assert all(0 <= s.ids["queue_depth"] <= 2 for s in asked)
    h2d_end = {s.ids["batch"]: s.end_ns for s in by["H2D"]}
    assert all(h2d_end[s.ids["batch"]] <= s.end_ns for s in asked), \
        "a batch was handed over before its copy was done"
    # Self time of a turn: what its children do not cover, never negative.
    for st in steps:
        kids = sum(s.end_ns - s.start_ns for s in got if s.parent == st.id)
        assert 0 <= kids <= st.end_ns - st.start_ns
    assert reg.histogram("hvd_input_wait_seconds").count == waits + 4
    assert reg.counter("hvd_h2d_bytes_total").value == \
        h2d + 4 * (16 * 8 * 4 + 16 * 8)      # float32 inputs + int64 labels
    assert 0 <= reg.gauge("hvd_input_queue_depth").value <= 2


def test_fit_without_prefetch_or_callbacks_has_no_such_spans():
    got, _ = _fit(n=3, prefetch=0)
    names = {s.name for s in got}
    assert {"fit.step", "fit.next_batch", "fit.train_step",
            "step.dispatch"} <= names
    assert not names & {"fit.callbacks", "H2D", "input.source"}
    assert all("batch" not in s.ids for s in got
               if s.name == "fit.next_batch")


def test_plain_prefetch_loop_records_the_input_thread():
    hvd.init()
    mark = _mark()
    from horovod_tpu import runtime
    out = list(prefetch_to_device(iter(_batches(3)), 2,
                                  sharding=runtime.ranked_sharding()))
    assert len(out) == 3
    got = _since(mark)
    assert sorted(s.ids["batch"] for s in got if s.name == "H2D") == [0, 1, 2]
    assert len([s for s in got if s.name == "input.source"]) == 3


def test_compiles_counter_moves_on_a_new_shape_only():
    counter = registry().counter("hvd_compiles_total")
    f = jax.jit(lambda x: x * 2 + 1)
    seen, again, new = jnp.ones((3, 5)), jnp.ones((3, 5)), jnp.ones((4, 5))
    f(seen)
    n0 = counter.value
    mark = _mark()
    f(again)
    assert counter.value == n0 and not _since(mark)
    f(new)
    assert counter.value == n0 + 1
    compiled = [s for s in _since(mark) if s.name == "xla.compile"]
    assert len(compiled) == 1 and compiled[0].end_ns > compiled[0].start_ns


def test_a_second_trainer_on_a_warm_step_compiles_nothing():
    """What the fit cell of the benchmark does: warm up on one Trainer,
    measure on a new one. The loop's own small programs are shared."""
    step, state = _step_and_state()
    warm, state = _fit(n=2, step_and_state=(step, state))
    assert [s for s in warm if s.name == "xla.compile"]
    got, _ = _fit(n=2, step_and_state=(step, state))
    assert not [s for s in got if s.name == "xla.compile"]


# -- named scopes in the lowered step ------------------------------------------

def test_lowered_step_carries_forward_optimizer_and_bucket_scopes():
    hvd.init()
    assert hvd.size() > 1
    model = _MLP()
    state, dist_opt = training.create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 8)), optax.sgd(0.05))
    step = training.make_train_step(model, dist_opt)
    batch = training.shard_batch(_batches(1)[0])
    text = step.lower(state, batch).as_text(debug_info=True)
    for scope in ('"jvp(forward)/', '"transpose(jvp(forward))/',
                  '"optimizer/', '"optimizer/allreduce.bucket0/psum'):
        assert scope in text, scope


def test_parallel_transformer_step_carries_the_same_scopes():
    from horovod_tpu.parallel import transformer as ptr
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    hvd.init()
    cfg = ptr.TransformerConfig(vocab=64, d_model=32, n_heads=2, d_ff=64,
                                n_layers=1)
    mesh = create_hybrid_mesh(devices=jax.devices()[:2], dp=2)
    init_state, step = ptr.make_parallel_train_step(
        cfg, mesh, optax.sgd(0.1))
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 16), jnp.int32)
    text = step.lower(params, opt_state, tokens, tokens).as_text(
        debug_info=True)
    # dp = 2: the layer's bucket is reduced inside the backward and keeps
    # its scope there; what is left follows under the optimizer's.
    for scope in ('"jvp(forward)/', '"transpose(jvp(forward))/',
                  'transpose(jvp(forward))/optimizer/allreduce.bucket0/psum',
                  '"optimizer/allreduce.bucket1/psum'):
        assert scope in text, scope


# -- set-up: what jax does before the first step, by phase and function --------

def _phases(since, name):
    """The trace, lowering and compile of the function ``name`` among
    ``since`` (jax names the last two ``jit(<name>)``)."""
    return [s for s in since if s.name.startswith("xla.")
            and s.ids.get("fun") in (name, f"jit({name})")]


def test_a_fresh_function_leaves_a_trace_a_lowering_and_a_compile():
    seconds = registry().counter("hvd_compile_seconds_total",
                                 labels=("phase",))
    before = {p: seconds.labels(phase=p).value
              for p in ("trace", "lower", "compile")}

    def setup_phases_probe(x):
        return jnp.tanh(x) * 3 - 1

    f = jax.jit(setup_phases_probe)
    mark = _mark()
    f(jnp.ones((7, 3)))
    got = _phases(_since(mark), "setup_phases_probe")
    assert [s.name for s in got] == ["xla.trace", "xla.lower", "xla.compile"]
    assert [s.ids["fun"] for s in got] == [
        "setup_phases_probe", "jit(setup_phases_probe)",
        "jit(setup_phases_probe)"]
    trace, lower, compiled = got
    assert trace.start_ns < trace.end_ns <= lower.end_ns <= compiled.end_ns
    assert lower.start_ns < compiled.start_ns
    assert "cache" not in trace.ids and "cache" not in lower.ids
    main = threading.current_thread().ident
    assert {s.thread for s in got} == {main}
    for phase, s in zip(("trace", "lower", "compile"), got):
        grew = seconds.labels(phase=phase).value - before[phase]
        assert grew >= (s.end_ns - s.start_ns) / 1e9 * 0.99 > 0
    # A repeat of a compiled shape leaves nothing.
    mark = _mark()
    f(jnp.ones((7, 3)))
    assert not _since(mark)


def test_a_trace_inside_an_open_span_is_its_child():
    def nested_probe(x):
        return x + 2

    with tl.span("test.outer") as outer:
        jax.jit(nested_probe)(jnp.ones((2,)))
    inside = [s for s in tl.spans() if s.parent == outer.id]
    assert {"xla.trace", "xla.lower", "xla.compile"} <= {
        s.name for s in inside}


def test_compile_spans_say_what_the_persistent_cache_did(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache = registry().counter("hvd_compile_cache_total",
                               labels=("result",))

    def counted():
        """(hits, misses): clearing jax's caches makes the small programs
        around the probe compile again too, so these move by one or more."""
        return tuple(cache.labels(result=r).value for r in ("hit", "miss"))

    start = counted()
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)

    def cached_probe(x):
        return jnp.sin(x) + 41

    def compile_again():
        jax.clear_caches()
        mark = _mark()
        jax.jit(cached_probe)(jnp.ones((5,)))
        compiled = [s for s in _phases(_since(mark), "cached_probe")
                    if s.name == "xla.compile"]
        assert len(compiled) == 1
        return compiled[0].ids["cache"]

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        assert compile_again() == "miss"
        cold = counted()
        assert cold[0] == start[0] and cold[1] > start[1]
        assert compile_again() == "hit"
        warm = counted()
        assert warm[0] > cold[0] and warm[1] == cold[1]
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()
        assert compile_again() == "off"
        assert counted() == warm
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
        cc.reset_cache()


def test_import_is_one_span_and_nothing_starts_before_it():
    code = ("import json, horovod_tpu\n"
            "from horovod_tpu.utils import timeline\n"
            "print(json.dumps([[s.name, s.start_ns, s.end_ns, s.thread] "
            "for s in timeline.spans()]))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    imports = [s for s in got if s[0] == "hvd.import"]
    assert len(imports) == 1
    _, start, end, thread = imports[0]
    assert end - start > 100_000_000, "jax alone takes longer to import"
    assert all(s[1] >= start for s in got)
    # It is the last thing the import does: what jax traced while the
    # modules loaded lies inside it, on the same thread.
    assert got[-1][0] == "hvd.import"
    assert all(s[2] <= end and s[3] == thread for s in got)


def test_init_is_one_span_over_two_calls():
    hvd.shutdown()
    mark = _mark()
    hvd.init()
    hvd.init()
    inits = [s for s in _since(mark) if s.name == "hvd.init"]
    assert len(inits) == 1
    assert inits[0].ids == {"size": hvd.size()}
    assert inits[0].end_ns > inits[0].start_ns


# -- collector pauses ----------------------------------------------------------

def test_full_collections_are_spans_and_quick_young_ones_are_not():
    gc.collect()                       # leave little for the next ones
    mark = _mark()
    gc.collect()
    got = [s for s in _since(mark) if s.name == "host.gc"]
    assert len(got) == 1
    assert got[0].ids["generation"] == 2 and got[0].ids["collected"] >= 0
    assert got[0].end_ns > got[0].start_ns
    assert got[0].thread == threading.current_thread().ident
    mark = _mark()
    gc.collect(0)
    young = [s for s in _since(mark) if s.name == "host.gc"]
    assert all(s.end_ns - s.start_ns >= 1_000_000 for s in young), \
        "a collection under a millisecond left a span"
    assert not young or young[0].ids["generation"] == 0
