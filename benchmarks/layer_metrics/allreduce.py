"""``allreduce.ms_per_step`` and ``allreduce.exposed_ms``: the collective
ops on device 0 — the time their (merged) intervals cover, and the part of
it during which no other op ran on that device — over the steps traced.
Layer: collective plan."""

from lib import trace as tr

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    """By opcode (``all-reduce``, ``all-reduce-start``, ``-done``), never
    by substring of the text: consumers name ``%all-reduce.N`` among their
    operands."""
    return tr.opcode(name).startswith(COLLECTIVES)


def read(trace, run, cell):
    if cell["chips"] < 2:
        return {}
    total, exposed = tr.collective_ns(trace.devices[0], is_collective)
    if not total:
        return {}
    return {"allreduce.ms_per_step": total / 1e6 / run["steps"],
            "allreduce.exposed_ms": exposed / 1e6 / run["steps"]}
