"""Trainer: the ``model.fit`` analog driving the compiled step + callbacks.

Parity: the reference's training loops are Keras ``model.fit`` with Horovod
callbacks (``examples/keras_mnist_advanced.py:80-110``) or raw
``MonitoredTrainingSession`` loops (``examples/tensorflow_mnist.py:99-119``).
This Trainer is the thin host-side loop around the jitted SPMD train step:
epochs × steps, invoking :mod:`horovod_tpu.callbacks` hooks, rank-0-only
verbosity (``keras_imagenet_resnet50.py:59`` convention), and rank-0-only
checkpointing (SURVEY §5.4) via orbax.
"""

from __future__ import annotations

import atexit
import dataclasses
import os
import queue
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import runtime
from .obs import flightrec as _flightrec
from .obs.registry import registry as _metrics_registry
from .testing import faults as _faults
from .training import TrainState, make_batch_placer, shard_batch
from .utils import timeline as _timeline


@jax.jit
def _add_metrics(acc, metrics):
    """The running-metric reducer's one program, shared by every Trainer:
    a per-instance ``jax.jit(lambda ...)`` would compile again for each new
    Trainer (one ``xla.compile`` inside a warmed loop's first step)."""
    return jax.tree_util.tree_map(
        lambda a, x: a + jnp.asarray(x, jnp.float32), acc, metrics)


class Trainer:
    """Host training loop; owns the mutable ``state`` that callbacks adjust."""

    def __init__(self, train_step: Callable, state: TrainState,
                 *, eval_step: Optional[Callable] = None,
                 steps_per_epoch: Optional[int] = None,
                 verbose: Optional[bool] = None,
                 prefetch: int = 2,
                 max_bad_steps: Optional[int] = None,
                 elastic: Any = None,
                 resize: Any = None):
        self.train_step = train_step
        self.eval_step = eval_step
        self.state = state
        self.steps_per_epoch = steps_per_epoch
        if verbose is None:
            verbose = (not runtime.is_initialized()
                       or runtime.world().controller_rank == 0)
        self.verbose = verbose
        # Background input staging depth (0 disables): keeps `prefetch`
        # sharded batches ahead of the step so chips never wait on host
        # input (see horovod_tpu.data).
        self.prefetch = prefetch
        self.history: List[Dict[str, float]] = []
        # Global step counter across epochs — drives the deterministic
        # fault-injection hook (testing/faults.py; no-op in production).
        self._global_step = 0
        # Device-resident running-metric reducer (_add_metrics): epoch logs
        # come from one (sums, count) accumulator updated per step, not an
        # O(steps) host list of device arrays fetched in a storm at epoch
        # end. The add is a tiny jitted program so the step loop never
        # synchronizes on a metric value.
        self._eval_placer: Optional[Callable] = None
        # Bad-step containment (active only when the train step was built
        # with guard_nonfinite and emits the ``bad_step`` metric): a
        # device-resident consecutive-skip counter, the budget beyond
        # which a NaN storm stops being "transient" (HVD_MAX_BAD_STEPS),
        # and optionally an ElasticState to roll back onto — its verified
        # fallback walk guarantees the rollback target's bytes are good.
        from .utils import config as _config
        self.max_bad_steps = (_config.max_bad_steps()
                              if max_bad_steps is None
                              else max(1, int(max_bad_steps)))
        self.elastic = elastic
        # Live-resize quiesce hook (horovod_tpu.elastic.ResizeCoordinator):
        # polled once per completed step — one atomic load on the hot path.
        # When a resize executes, the current epoch ends early (its input
        # stream was sharded for the OLD world) and the next epoch runs on
        # the re-formed world with the rebuilt train step.
        self.resize = resize
        self._bad_counter = None
        self._bad_add = None
        # Hot-path metrics (horovod_tpu.obs): registered once here — a
        # per-step registry lookup would be dict hashing on the hot loop
        # for nothing. Names are API (docs/observability.md).
        reg = _metrics_registry()
        self._m_steps = reg.counter(
            "hvd_steps_total",
            "Train steps completed by this rank's loop (skipped "
            "bad steps included — they consumed a batch)")
        self._m_step_seconds = reg.histogram(
            "hvd_step_seconds",
            "Per-step wall time: input wait + dispatch + host-side work "
            "between consecutive step completions")
        self._m_input_wait = reg.histogram(
            "hvd_input_wait_seconds",
            "What next(batch) blocked the step loop for (the part of "
            "hvd_step_seconds spent waiting on the input pipeline)")
        self._m_samples = reg.counter(
            "hvd_samples_total",
            "Training examples consumed (leading batch-axis rows seen "
            "by this process's loop)")
        self._m_bad = reg.counter(
            "hvd_bad_steps_total",
            "Steps skipped by the non-finite gradient guard")
        self._m_epochs = reg.counter("hvd_epochs_total",
                                     "Epochs completed")
        self._m_gstep = reg.gauge(
            "hvd_global_step",
            "Global step counter (across epochs and restarts of this "
            "process)")

    def _stream(self, data: Iterable):
        from .data import prefetch_to_device, shard_iterator
        if self.prefetch and self.prefetch > 0:
            if runtime.is_initialized() and not runtime.world().env_world:
                # Hand the prefetch thread the world sharding so the
                # host→device copy of batch k+1 overlaps step k on the
                # device, instead of happening synchronously at next().
                return prefetch_to_device(
                    iter(data), self.prefetch,
                    sharding=runtime.ranked_sharding())
            return prefetch_to_device(shard_iterator(data), self.prefetch)
        return shard_iterator(data)

    # -- running metrics (device-resident, fetched once per epoch) ---------

    def _accumulate_metrics(self, sums, metrics):
        if sums is None:
            for k in metrics:
                for leaf in jax.tree_util.tree_leaves(metrics[k]):
                    if np.ndim(leaf) != 0:
                        raise ValueError(
                            f"train-step metric {k!r} has shape "
                            f"{np.shape(leaf)}; metrics_fn must return "
                            f"scalar leaves (reduce to a per-batch mean "
                            f"before returning) — a non-scalar here would "
                            f"silently broadcast into the epoch mean")
            sums = jax.tree_util.tree_map(
                lambda x: jnp.zeros((), jnp.float32), metrics)
        return _add_metrics(sums, metrics)

    # -- bad-step containment (guard_nonfinite train steps) ----------------

    def _track_bad_step(self, bad_flag) -> bool:
        """Fold this step's ``bad_step`` flag into the device-resident
        consecutive-skip counter; returns True when the step was skipped.
        The ``int()`` fetch of one scalar per step is the whole host-side
        cost of containment. Exceeding ``max_bad_steps`` consecutive
        skips triggers :meth:`_contain` (rollback or raise)."""
        if self._bad_add is None:
            self._bad_add = jax.jit(
                lambda c, b: jnp.where(jnp.asarray(b) > 0, c + 1,
                                       jnp.zeros_like(c)))
            self._bad_counter = jnp.zeros((), jnp.int32)
        self._bad_counter = self._bad_add(self._bad_counter, bad_flag)
        consec = int(self._bad_counter)
        if consec == 0:
            return False
        tl = runtime.world().timeline if runtime.is_initialized() else None
        with _timeline.maybe_op(tl, "train.guard", _timeline.BAD_STEP):
            pass  # instantaneous marker: this step was skipped
        self._m_bad.inc()
        _flightrec.record("bad_step", step=self._global_step,
                          consecutive=consec)
        if self.verbose:
            print(f"[trainer] non-finite gradients at global step "
                  f"{self._global_step}: update skipped "
                  f"({consec}/{self.max_bad_steps} consecutive)",
                  file=sys.stderr, flush=True)
        if consec >= self.max_bad_steps:
            self._contain(consec)
        return True

    def _contain(self, consec: int) -> None:
        """The bad-step budget is exhausted: the params (or the data
        pipeline feeding them) are presumed poisoned beyond what
        skip-steps can absorb. With an attached
        :class:`~horovod_tpu.elastic.ElasticState`, roll back to the last
        checkpoint that PASSES integrity verification (the fallback walk)
        and keep training; without one, raise
        :class:`~horovod_tpu.exceptions.NonFiniteGradError` — skipping
        forever would burn the reservation training nothing."""
        from .exceptions import NonFiniteGradError
        if self.elastic is None:
            raise NonFiniteGradError(
                f"{consec} consecutive non-finite-gradient steps at "
                f"global step {self._global_step} and no elastic state "
                f"to roll back to — a persistent NaN source (bad data "
                f"shard, broken loss scale, flaky chip) will not fix "
                f"itself. Attach Trainer(elastic=ElasticState(...)) for "
                f"automatic rollback, or raise HVD_MAX_BAD_STEPS if "
                f"longer transients are expected")
        es = self.elastic
        # Current trees as restore templates (structure + sharding); the
        # restore overwrites every value from the verified checkpoint.
        es.params, es.opt_state = self.state.params, self.state.opt_state
        try:
            es.restore()   # latest_committed's walk skips corrupt steps
        except FileNotFoundError as e:
            # Elastic attached but nothing committed (or every commit
            # corrupt): same terminal diagnosis as the no-elastic branch
            # — a filesystem error would send the user hunting paths
            # instead of the NaN source.
            raise NonFiniteGradError(
                f"{consec} consecutive non-finite-gradient steps at "
                f"global step {self._global_step} and no verified "
                f"committed checkpoint to roll back to ({e}) — commit "
                f"via ElasticState before the storm, or fix the NaN "
                f"source (bad data shard, broken loss scale, flaky "
                f"chip)") from e
        self.state = dataclasses.replace(
            self.state, params=es.params, opt_state=es.opt_state,
            step=jnp.asarray(es.step, self.state.step.dtype))
        self._bad_counter = jnp.zeros((), jnp.int32)
        _flightrec.record("rollback", step=es.step,
                          consecutive_bad=consec)
        if self.verbose:
            print(f"[trainer] bad-step budget exhausted ({consec} "
                  f"consecutive skips) — rolled back to verified "
                  f"elastic step {es.step}", file=sys.stderr, flush=True)

    def _maybe_resize(self) -> bool:
        """The step-boundary quiesce hook of the live-resize plane: sync
        the live trees into the elastic state, let the
        :class:`~horovod_tpu.elastic.ResizeCoordinator` poll (one atomic
        load when nothing is pending) and — once the world-wide quiesce
        step is reached — execute the in-place resize. Returns True when
        the world was just re-formed (the caller must abandon the current
        epoch's input stream)."""
        import numpy as np
        step = int(self.state.step)
        rc = self.resize
        req = rc.poll(step)
        if req is None or not rc.due(step):
            return False
        # batch_stats are not part of the committed elastic state; carry
        # them across the re-form host-side (re-placed replicated — the
        # rebuild's train step re-shards them on first use if it must).
        host_bs = None
        if self.state.batch_stats is not None:
            host_bs = jax.tree_util.tree_map(np.asarray,
                                             self.state.batch_stats)
        rebuilt = rc.step_boundary(step, params=self.state.params,
                                   opt_state=self.state.opt_state)
        if rebuilt is None:
            return False
        new_bs = None
        if host_bs is not None:
            new_bs = jax.tree_util.tree_map(jnp.asarray, host_bs)
        self.state = dataclasses.replace(
            self.state, params=rc.state.params,
            opt_state=rc.state.opt_state, batch_stats=new_bs,
            step=jnp.asarray(rc.state.step, self.state.step.dtype))
        if rebuilt.train_step is not None:
            self.train_step = rebuilt.train_step
        _flightrec.record("resize_executed", step=int(self.state.step),
                          world=runtime.size()
                          if runtime.is_initialized() else None)
        # Mesh-tied host-side caches die with the old world.
        self._eval_placer = None
        self._bad_add = None
        self._bad_counter = None
        if self.verbose:
            print(f"[trainer] live resize executed at step "
                  f"{int(self.state.step)}; epoch ends early, training "
                  f"resumes on the new world", file=sys.stderr, flush=True)
        return True

    def fit(self, data: Callable[[], Iterable], epochs: int = 1,
            callbacks: Optional[List] = None,
            eval_data: Optional[Callable[[], Iterable]] = None,
            initial_epoch: int = 0):
        """Run the training loop.

        Args:
          data: zero-arg callable returning a fresh per-epoch iterable of
            ``(inputs, labels)`` host batches (global batch; sharded here).
          epochs: final epoch (exclusive).
          callbacks: list of :class:`horovod_tpu.callbacks.Callback`.
          eval_data: optional eval-batch iterable factory, run at epoch end.
          initial_epoch: first epoch — nonzero after checkpoint resume (the
            reference broadcasts the resume epoch from rank 0,
            ``keras_imagenet_resnet50.py:47-56``).
        """
        callbacks = list(callbacks or [])
        for cb in callbacks:
            cb.set_trainer(self)

        for cb in callbacks:
            cb.on_train_begin()
        for epoch in range(initial_epoch, epochs):
            t0 = time.perf_counter()
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            nsteps = 0
            bad_steps = 0
            guard_active = False
            resized_early = False
            metric_sums = None
            stream = self._stream(data())
            batches = iter(stream)
            batch_idx = 0
            step_t0 = time.perf_counter()
            try:
                while True:
                    # One turn of the loop is one ``fit.step`` span; its
                    # children name where the turn went (next batch, the
                    # train step call, callbacks) and what is left over is
                    # the loop's own bookkeeping below.
                    with _timeline.span("fit.step",
                                        step=self._global_step) as turn:
                        with _timeline.span("fit.next_batch") as waited:
                            try:
                                batch = next(batches)
                            except StopIteration:
                                waited.drop()
                                turn.drop()
                                break
                        self._m_input_wait.observe(
                            (waited.end_ns - waited.start_ns) * 1e-9)
                        if self.steps_per_epoch is not None \
                                and batch_idx >= self.steps_per_epoch:
                            turn.drop()
                            break
                        if callbacks:
                            with _timeline.span("fit.callbacks"):
                                for cb in callbacks:
                                    cb.on_batch_begin(batch_idx)
                        with _timeline.span("fit.train_step"):
                            self.state, metrics = self.train_step(
                                self.state, batch)
                        # The guard's flag rides the metrics dict but is a
                        # count, not a mean — pop it before the epoch
                        # accumulator sees it.
                        bad_flag = (metrics.pop("bad_step", None)
                                    if isinstance(metrics, dict) else None)
                        metric_sums = self._accumulate_metrics(metric_sums,
                                                               metrics)
                        if bad_flag is not None:
                            guard_active = True
                            if self._track_bad_step(bad_flag):
                                bad_steps += 1
                        if callbacks:
                            with _timeline.span("fit.callbacks"):
                                for cb in callbacks:
                                    cb.on_batch_end(batch_idx)
                        nsteps += 1
                        # Telemetry: per-step wall time (completion to
                        # completion — input wait included, it is the
                        # number an operator acts on), throughput
                        # counters, and one flight-recorder event naming
                        # the step a post-mortem will call "last
                        # completed".
                        now = time.perf_counter()
                        self._m_step_seconds.observe(now - step_t0)
                        step_t0 = now
                        self._m_steps.inc()
                        # Post-increment count: the gauge reads "steps
                        # this process has completed" (the fleet poller's
                        # straggler spread keys on it).
                        self._m_gstep.set(self._global_step + 1)
                        try:
                            rows = int(np.shape(
                                jax.tree_util.tree_leaves(batch)[0])[0])
                        except (IndexError, TypeError):
                            rows = 0
                        if rows:
                            self._m_samples.inc(rows)
                        _flightrec.record("step", step=self._global_step,
                                          epoch=epoch)
                        _faults.step_hook(self._global_step)
                        self._global_step += 1
                        batch_idx += 1
                        if self.resize is not None and self._maybe_resize():
                            # World re-formed in place: the rest of this
                            # epoch's stream is sharded for the old world
                            # — end the epoch here, resume on the new
                            # world.
                            resized_early = True
                            break
            finally:
                close = getattr(stream, "close", None)
                if close is not None:
                    close()
            if self.steps_per_epoch is None and not resized_early:
                # A resize-truncated epoch must not be recorded as the
                # inferred epoch length — it would silently cap every
                # later epoch at the truncation point.
                self.steps_per_epoch = nsteps

            # Epoch logs are the running mean over the epoch's batches (the
            # Keras fit semantics the reference callbacks assume), not the
            # last batch — ReduceLROnPlateau/MetricAverage need a stable
            # signal, not one noisy step. One device fetch for the whole
            # epoch: the (sums, count) accumulator replaces the former
            # per-step list whose epoch-end np.mean forced a sync per
            # retained step.
            logs: Dict[str, float] = {}
            if metric_sums is not None:
                # Skipped steps contributed zeros to every metric sum (the
                # step zeroes NaN-bearing metrics on a skip), so the mean
                # is over the steps that actually trained.
                good = max(1, nsteps - bad_steps)
                for k, v in jax.device_get(metric_sums).items():
                    logs[k] = float(v) / good
            if guard_active:
                logs["bad_steps"] = float(bad_steps)
            if eval_data is not None and self.eval_step is not None:
                if self._eval_placer is None:
                    # Hoisted: mesh lookup + NamedSharding construction
                    # happen once, not per eval batch per epoch.
                    self._eval_placer = make_batch_placer()
                evals = []
                for b in eval_data():
                    rows = int(np.shape(
                        jax.tree_util.tree_leaves(b)[0])[0])
                    evals.append((rows, self.eval_step(
                        self.state, self._eval_placer(b))))
                if evals:  # the eval iterable can be empty at large world sizes
                    total = sum(r for r, _ in evals)
                    for k in evals[0][1]:
                        logs[f"val_{k}"] = float(sum(
                            r * np.asarray(e[k]) for r, e in evals) / total)
            for cb in callbacks:
                cb.on_epoch_end(epoch, logs)
            self._m_epochs.inc()
            self.history.append(logs)
            if self.verbose:
                dt = time.perf_counter() - t0
                msg = " ".join(f"{k}={v:.4f}" for k, v in logs.items())
                print(f"epoch {epoch + 1}/{epochs} [{dt:.1f}s, "
                      f"{nsteps} steps] {msg}")
        for cb in callbacks:
            cb.on_train_end()
        return self.history


# ---------------------------------------------------------------------------
# Checkpoint / resume — rank-0-only write + broadcast-on-restore (SURVEY §5.4).
# ---------------------------------------------------------------------------

class AsyncCheckpointer:
    """Background checkpoint writer: the step loop pays only the
    device→host snapshot; serialization happens off the critical path.

    The synchronous ``save_checkpoint`` stalls the TPU for the whole orbax
    write (seconds at real model sizes, every epoch). The async protocol
    splits the save at the only point that needs the live state:

    1. **snapshot** (caller thread, ``CKPT_SNAPSHOT`` timeline phase) —
       ``jax.device_get`` the state into host numpy. The training loop can
       mutate/donate device state freely afterwards.
    2. **write** (this writer's thread, ``CKPT_WRITE`` phase) — orbax
       serialization + retention GC of the immutable host copy.
    3. **durable hook** — ``on_durable`` runs only after the write
       succeeded; the elastic two-phase commit hangs its marker file here,
       so a crash mid-write can never leave a marker pointing at torn
       bytes (the PR-1 contract, :mod:`horovod_tpu.elastic`).

    ``wait()`` blocks until every submitted write is durable and re-raises
    the first writer error; ``close()`` waits, stops the thread, and makes
    further submits fail. ``max_pending`` bounds host memory: the queue
    holds at most that many snapshots before ``submit`` backpressures.

    The writer thread is a daemon (a wedged orbax write must never hang
    interpreter exit), so an exit without ``close()`` — including an
    exception unwinding past the training loop — would silently drop
    queued writes; an ``atexit`` hook drains them best-effort (bounded
    wait, errors logged not raised). Prefer an explicit ``close()`` /
    ``with`` block: only those re-raise writer failures.
    """

    def __init__(self, max_pending: int = 2,
                 timeline: Optional[Any] = None):
        if timeline is None and runtime.is_initialized():
            timeline = runtime.world().timeline
        self.timeline = timeline
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, max_pending))
        self._errors: List[BaseException] = []
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="hvd-ckpt-writer", daemon=True)
        self._thread.start()
        atexit.register(self._drain_at_exit)

    def submit(self, write_fn: Callable[[], Any],
               on_durable: Optional[Callable[[], Any]] = None) -> None:
        """Enqueue a write job (host data must already be snapshotted).
        Blocks only when ``max_pending`` writes are already in flight."""
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        self._q.put((write_fn, on_durable))

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                write_fn, on_durable = item
                try:
                    with _timeline.maybe_op(self.timeline, "ckpt.write",
                                            _timeline.CKPT_WRITE):
                        write_fn()
                    if on_durable is not None:
                        on_durable()
                except BaseException as e:  # noqa: BLE001 — to wait()
                    self._errors.append(e)
            finally:
                self._q.task_done()

    def wait(self, timeout: Optional[float] = None) -> None:
        """Barrier: returns once every submitted write is durable on disk,
        re-raising the first writer failure. Call before any restore (or
        before trusting the directory contents) — async means the bytes
        land later, not that they may not land.

        With ``timeout`` (seconds), a write still in flight when the
        deadline expires raises
        :class:`~horovod_tpu.exceptions.CheckpointTimeoutError` instead
        of blocking forever on a hung filesystem — the write itself is
        NOT cancelled (the thread keeps going; a later ``wait()`` sees
        its eventual outcome), but the caller gets control back to page
        a human or fail over."""
        if timeout is None:
            self._q.join()
        else:
            deadline = time.monotonic() + timeout
            # queue.Queue.join() has no timeout; wait on the same
            # all_tasks_done condition it uses, with a deadline.
            with self._q.all_tasks_done:
                while self._q.unfinished_tasks:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        from .exceptions import CheckpointTimeoutError
                        raise CheckpointTimeoutError(
                            f"checkpoint write still in flight after "
                            f"{timeout:.1f}s — filesystem hung or writer "
                            f"wedged ({self._q.unfinished_tasks} job(s) "
                            f"pending); the write was NOT cancelled")
                    self._q.all_tasks_done.wait(remaining)
        if self._errors:
            raise self._errors.pop(0)

    def close(self) -> None:
        """Drain pending writes, stop the thread, surface any error.

        Like ``wait()`` this is a durability barrier: it blocks until every
        pending write lands, HOWEVER long that takes — a wedged write (dead
        NFS) holds ``close()`` rather than returning with bytes not
        durable. The bounded-exit protection lives one layer down: the
        daemon thread plus the atexit drain keep an *unclosed* writer from
        hanging interpreter shutdown."""
        atexit.unregister(self._drain_at_exit)
        if self._closed:
            self._thread.join(timeout=60)
            if self._errors:
                raise self._errors.pop(0)
            return
        self._closed = True
        self._q.put(None)
        self._q.join()
        self._thread.join(timeout=60)
        if self._errors:
            raise self._errors.pop(0)

    def _drain_at_exit(self) -> None:
        """Bounded best-effort drain at interpreter shutdown: the queue's
        pending writes run before the stop sentinel, and the join timeout
        keeps a wedged write from hanging exit (the reason the thread is
        a daemon in the first place)."""
        if self._closed or not self._thread.is_alive():
            return
        self._closed = True
        try:
            self._q.put(None, timeout=60)
        except queue.Full:
            return
        self._thread.join(timeout=60)
        for e in self._errors:
            print(f"[hvd-ckpt-writer] checkpoint write failed at exit: {e!r}",
                  file=sys.stderr)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def save_checkpoint(directory: str, state: TrainState,
                    step: Optional[int] = None,
                    max_to_keep: Optional[int] = None,
                    writer: Optional[AsyncCheckpointer] = None
                    ) -> Optional[str]:
    """Write a checkpoint — rank 0 only, like the reference
    (``checkpoint_dir=None`` on other ranks, ``README.md:78-80``).
    Returns the path written, or None on non-root ranks.

    ``max_to_keep``: after a successful write, delete the oldest
    checkpoints beyond the newest ``max_to_keep`` (retention is the
    writer's job since only rank 0 touches the directory).

    With ``writer`` (an :class:`AsyncCheckpointer`), only the device→host
    snapshot happens here; the orbax write and retention GC run on the
    writer's thread while training continues. The returned path is durable
    only after ``writer.wait()``.
    """
    if runtime.is_initialized() and runtime.world().controller_rank != 0:
        return None
    import orbax.checkpoint as ocp
    step = int(state.step) if step is None else step
    path = os.path.join(os.path.abspath(directory), f"ckpt_{step}")
    from .parallel.checkpoint import snapshot_to_host
    tl = writer.timeline if writer is not None else (
        runtime.world().timeline if runtime.is_initialized() else None)
    host = snapshot_to_host(state, timeline=tl)

    def _write():
        # orbax writes into a tmp dir and renames on finalize, so a writer
        # killed mid-write never leaves a visible ckpt_<step> for the
        # latest-step restore scan to trust. The integrity manifest lands
        # right after the rename — before any elastic marker (which hangs
        # off the writer's on_durable hook, strictly later).
        from .parallel.checkpoint import write_manifest
        ocp.PyTreeCheckpointer().save(path, host, force=True)
        write_manifest(path, host, step=step)
        apply_retention(directory, path, max_to_keep)

    if writer is None:
        with _timeline.maybe_op(tl, "ckpt.write", _timeline.CKPT_WRITE):
            _write()
    else:
        writer.submit(_write)
    return path


def apply_retention(directory: str, just_written: str,
                    max_to_keep: Optional[int]) -> None:
    """Delete the oldest checkpoints beyond the newest ``max_to_keep``.

    Retention by WRITE recency, not step number: a run resumed from a
    rolled-back step must never have its just-written checkpoint deleted
    in favor of stale higher-step leftovers. Shared by the replicated-DP
    writer above and the sharded writer
    (:mod:`horovod_tpu.parallel.checkpoint`) — one policy, one bug
    surface.
    """
    if max_to_keep is None or max_to_keep <= 0:
        return
    import shutil
    base = os.path.abspath(directory)
    entries = []
    for n in os.listdir(base):
        if _step_of(n) is None:
            continue
        full = os.path.join(base, n)
        try:
            entries.append((os.path.getmtime(full), full))
        except OSError:
            continue
    entries.sort()
    for _, old in entries[:-max_to_keep]:
        if old != just_written:
            shutil.rmtree(old, ignore_errors=True)


def _step_of(name: str) -> Optional[int]:
    if not name.startswith("ckpt_"):
        return None
    try:
        return int(name.split("_", 1)[1])
    except ValueError:
        return None


def latest_checkpoint_step(directory: str) -> Optional[int]:
    """Find the newest checkpoint's step (the resume scan rank 0 performs
    before broadcasting the epoch, ``keras_imagenet_resnet50.py:47-56``)."""
    if not os.path.isdir(directory):
        return None
    steps = [s for s in (_step_of(n) for n in os.listdir(directory))
             if s is not None]
    return max(steps) if steps else None


def restore_checkpoint(directory: str, state: TrainState,
                       step: Optional[int] = None,
                       verify: bool = True) -> TrainState:
    """Restore (on every rank, from the shared filesystem) then broadcast
    from rank 0 so all ranks are bit-identical — the reference's
    load-on-rank-0 + ``BroadcastGlobalVariablesCallback`` protocol
    (``keras_imagenet_resnet50.py:130-133``).

    ``verify`` (default on) checks the checkpoint's integrity manifest
    first and raises
    :class:`~horovod_tpu.exceptions.CheckpointCorruptError` naming the
    offending leaf instead of resuming from torn/bit-rotted bytes;
    manifest-less legacy checkpoints restore unverified."""
    import orbax.checkpoint as ocp
    from .optimizer import broadcast_global_variables
    if step is None:
        step = latest_checkpoint_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = os.path.join(os.path.abspath(directory), f"ckpt_{step}")
    if verify:
        from .parallel.checkpoint import verify_checkpoint
        verify_checkpoint(path)
    ckptr = ocp.PyTreeCheckpointer()
    restored = ckptr.restore(
        path, item=jax.tree_util.tree_map(np.asarray, state))
    if runtime.is_initialized() and runtime.size() > 1:
        restored = broadcast_global_variables(restored, root_rank=0)
    return restored
