"""``device.idle_pct``: 1 - busy / traced window, averaged over the chips
used. Busy is the union of each device's op intervals; the window is the
host clock's, from the first dispatch to the last step's completion.
Layer: device."""

from lib import trace as tr


def read(trace, run, cell):
    return {"device.idle_pct":
            100.0 * (1.0 - tr.mean_busy_s(trace) / run["window_s"])}
