"""What the harness hands a family and a driver (:class:`Context`), and what
a driver hands back (:class:`Window`)."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Git-ignored, inside the checkout, fixed.
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


@dataclasses.dataclass
class Context:
    """What a family and a driver get from the harness."""
    cell: dict            # BENCHMARK.json's workload entry
    config: dict          # the configuration's file
    traffic: dict         # the traffic mix's file
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    compile_s: float = 0.0

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def log(self, **fields):
        """An earlier line of stdout (never the result line)."""
        if self.rehearse:
            fields["rehearsal"] = True
        print(json.dumps(fields), flush=True)

    @contextlib.contextmanager
    def compiling(self, what: str):
        """Clock around the first call of a compiled program (cache hit or
        miss): summed into the per-layer metric ``setup.compile_s``."""
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self.compile_s += dt
        self.log(event="first_call", what=what, seconds=dt)

    def start_trace(self) -> str:
        """Start the profiler into a fresh directory inside the checkout,
        with the options of ``lib/profiler_options.json`` (fields of
        ``jax.profiler.ProfileOptions``), and return the directory.

        The host tracer is off there: with it on (at any level) the
        runtime's host-side ``Transpose`` of each 77 MB batch records 5.2
        million events in 10 s on the input thread and the ResNet-50 fit
        cell runs 8.4x slower (PERF.md section 6, PR 23). The device's op
        lines do not need it. The price is that the harness's own
        ``bench.*`` spans are not recorded, so idle gaps come out
        ``unattributed`` until the program has cheaper spans of its own."""
        import jax
        path = os.path.join(TRACE_DIR, self.cell["name"])
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        options = jax.profiler.ProfileOptions()
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "profiler_options.json")) as fh:
            for key, value in json.load(fh).items():
                setattr(options, key, value)
        if self.rehearse:
            # The CPU backend's ops are host events: a rehearsal's stand-in
            # device needs the host tracer.
            options.host_tracer_level = 2
        jax.profiler.start_trace(path, profiler_options=options)
        return path


@dataclasses.dataclass
class Window:
    """What a driver measured. Times are ``time.perf_counter()`` readings;
    ``done_t[i]`` is when the DEVICE completed step i."""
    t_start: float
    done_t: list
    losses: list
    units_per_step: int           # tokens or images, over all chips
    rate_metric: str
    checks: dict                  # name -> bool; all must hold for `correct`
    failed: int = 0
    extra: dict = dataclasses.field(default_factory=dict)
    trace_dir: str | None = None
