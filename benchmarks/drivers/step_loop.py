"""Driver ``step_loop``: a closed loop that dispatches one compiled step per
turn on a pool of host batches made from the seed, cycled. Each turn
``device_put``s its batch and dispatches; no in-graph loop. A step's time is
the time between the DEVICE's completions of consecutive steps, taken by
blocking on the loss of the step two before the one just dispatched, so
timing never drains the queue (the state is donated and cannot be blocked
on).

Traffic parameters: ``pool`` (host batches), ``batch_per_chip``,
``seq_len`` (family ``lm``), ``mesh``, ``warmup_steps``, ``trace_seconds``.
"""

from __future__ import annotations

import time

import jax

LAG = 2


def run(ctx, family_module):
    import horovod_tpu as hvd
    from lib.cell import Window
    hvd.init(devices=ctx.devices)
    fam = family_module.build(ctx)
    t = ctx.traffic
    pool = fam.make_pool(t["pool"])
    state = fam.init()
    fam.compile(state, fam.place(pool[0]))
    checks = {"reference": fam.reference_check(state)}

    def turn(state, i):
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            batch = fam.place(pool[i % len(pool)])
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return fam.step(state, batch)

    for i in range(t.get("warmup_steps", 3)):
        state, loss = turn(state, i)
    jax.block_until_ready(loss)

    seconds, trace_dir = ctx.seconds, None
    if ctx.trace:
        seconds = min(seconds, t.get("trace_seconds", 10))
        trace_dir = ctx.start_trace()

    losses, done_t = [], []
    with jax.profiler.TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        i = 0
        while True:
            state, loss = turn(state, i)
            losses.append(loss)
            if i >= LAG:
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(losses[i - LAG])
                done_t.append(time.perf_counter())
            i += 1
            if time.perf_counter() - t_start >= seconds:
                break
        for loss in losses[len(done_t):]:
            jax.block_until_ready(loss)
            done_t.append(time.perf_counter())
    if ctx.trace:
        jax.profiler.stop_trace()

    if ctx.chips > 1:
        checks["replicas_equal"] = fam.replicas_equal(state)
    hvd.shutdown()
    return Window(t_start=t_start, done_t=done_t,
                  losses=[float(x) for x in losses],
                  units_per_step=fam.units_per_step,
                  rate_metric=family_module.RATE_METRIC, checks=checks,
                  extra={"batch_per_chip": t["batch_per_chip"],
                         "seq_len": t.get("seq_len")},
                  trace_dir=trace_dir)
