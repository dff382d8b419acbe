"""``fit.input_wait_ms`` and ``fit.dispatch_ms``: medians per step of the
``fit_host`` driver's callback clock. Input wait is on_batch_end(i) ->
on_batch_begin(i+1): ``next(stream)`` plus the loop's telemetry. Dispatch is
on_batch_begin(i) -> on_batch_end(i): the ``train_step`` call. Layer: host
loop."""

import statistics


def read(trace, run, cell):
    fit = run.get("fit")
    if not fit or not fit["input_wait_ms"]:
        return {}
    return {"fit.input_wait_ms": statistics.median(fit["input_wait_ms"]),
            "fit.dispatch_ms": statistics.median(fit["dispatch_ms"])}
