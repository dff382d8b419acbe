"""``device_step_ms.<family>``: device busy time (the union of the op
intervals on device 0's op line) over the steps of the traced window.
Layer: step builders."""

from lib import trace as tr


def read(trace, run, cell):
    busy_ms = tr.busy_ns(trace.devices[0]) / 1e6
    return {f"device_step_ms.{cell['config']['family']}":
            busy_ms / run["steps"]}
