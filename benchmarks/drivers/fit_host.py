"""Driver ``fit_host``: host batches through ``Trainer.fit`` — the flagship
example's own path: ``create_train_state`` -> ``make_train_step`` ->
``Trainer(step, state)`` with its default ``prefetch`` -> ``fit``, fed by a
generator that cycles a pool of float32 numpy batches made from the seed
and stops at the deadline.

``Trainer.fit`` fixes ``steps_per_epoch`` from the first epoch it runs, so
the warm-up uses one ``Trainer`` and the window a new one on the warmed
state. The callbacks get no metrics and the state is donated, so a step's
completion is taken from a small array computed from the fresh state
(``state.step + 0``), blocked on two steps later: timing never drains the
queue.

Traffic parameters: ``pool``, ``batch``, ``warmup_steps``,
``trace_seconds``, ``reference_images``.
"""

from __future__ import annotations

import time

import jax

LAG = 2


def _make_clock(ending=lambda: False):
    from horovod_tpu import callbacks

    class Clock(callbacks.Callback):
        """on_batch_end(i) -> on_batch_begin(i+1) is next(stream) plus the
        loop's telemetry (input wait); on_batch_begin(i) -> on_batch_end(i)
        is the train_step call (dispatch)."""

        def __init__(self, ending=lambda: False):
            self.begin, self.end, self.left = [], [], []
            self.markers, self.done_t = [], []
            self.ending = ending

        def on_batch_begin(self, batch, logs=None):
            self.begin.append(time.perf_counter())

        def on_batch_end(self, batch, logs=None):
            self.end.append(time.perf_counter())
            self.markers.append(self.trainer.state.step + 0)
            # Once the stream has ended nothing more will be dispatched
            # behind these steps: take each completion as it comes.
            self.sync(keep=0 if self.ending() else LAG)
            self.left.append(time.perf_counter())

        def sync(self, keep=0):
            """Block on every marker but the newest ``keep``."""
            while len(self.done_t) < len(self.markers) - keep:
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(self.markers[len(self.done_t)])
                self.done_t.append(time.perf_counter())

    return Clock(ending)


def run(ctx, family_module):
    import horovod_tpu as hvd
    from horovod_tpu import trainer
    from lib.cell import Window
    hvd.init(devices=ctx.devices)
    fam = family_module.build(ctx)
    t = ctx.traffic
    pool = fam.make_pool(t["pool"])
    state = fam.init()
    checks = {"reference": fam.reference_check(state)}

    # Warm-up: the step's compile and every small program of the loop and
    # of the clock, on a Trainer of its own.
    warm = trainer.Trainer(fam.train_step, state, verbose=False)
    n_warm = t.get("warmup_steps", 4)
    clock = _make_clock()
    with ctx.compiling("train_step_and_loop"):
        warm.fit(lambda: (pool[i % len(pool)] for i in range(n_warm)),
                 epochs=1, callbacks=[clock])
        clock.sync()
    state = warm.state

    seconds, trace_dir = ctx.seconds, None
    if ctx.trace:
        seconds = min(seconds, t.get("trace_seconds", 10))
        trace_dir = ctx.start_trace()

    def stream(deadline):
        i = 0
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                batch = pool[i % len(pool)]
            yield batch
            i += 1

    tr = trainer.Trainer(fam.train_step, state, verbose=False)
    with jax.profiler.TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        deadline = t_start + seconds
        clock = _make_clock(lambda: time.perf_counter() >= deadline)
        history = tr.fit(lambda: stream(deadline), epochs=1,
                         callbacks=[clock])
        clock.sync()
    if ctx.trace:
        jax.profiler.stop_trace()

    # The loop's own counter agrees with the clock's count of steps.
    checks["steps_counted"] = int(tr.state.step) - n_warm == len(clock.done_t)
    hvd.shutdown()
    wait_ms = [(b - a) * 1e3 for a, b in zip(clock.left, clock.begin[1:])]
    dispatch_ms = [(b - a) * 1e3 for a, b in zip(clock.begin, clock.end)]
    # fit() hands back epoch means only; per-step losses would need a fetch
    # per step. The falling-loss check reads the warm-up's and the window's
    # epoch means.
    losses = [warm.history[-1]["loss"], history[-1]["loss"]]
    return Window(t_start=t_start, done_t=clock.done_t, losses=losses,
                  units_per_step=fam.units_per_step,
                  rate_metric=family_module.RATE_METRIC, checks=checks,
                  extra={"fit": {"input_wait_ms": wait_ms,
                                 "dispatch_ms": dispatch_ms}},
                  trace_dir=trace_dir)
