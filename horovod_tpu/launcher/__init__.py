"""tpurun — the launcher replacing ``mpirun`` (reference: ``docs/running.md``).

The reference is launched as ``mpirun -np N -H host:slots python train.py``
with OpenMPI wiring rank/size env into every process. ``tpurun`` spawns one
process per chip on a TPU VM (or N local processes for CPU testing) and sets:

* ``HVD_RANK`` / ``HVD_SIZE`` / ``HVD_LOCAL_RANK`` — the process grid
  (parity: ``OMPI_COMM_WORLD_RANK`` etc., read by tests
  ``mpi_ops_test.py:31-63``).
* ``HVD_COORD_ADDR`` — rendezvous address of the host coordination plane
  (the out-of-band wire-up role MPI plays for the reference).
* with ``--jax-distributed``: ``JAX_COORDINATOR_ADDRESS`` /
  ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` so ``jax.distributed`` forms a
  global device mesh and *compiled* collectives span processes over ICI/DCN.
  Without it, processes are independent JAX worlds and cross-rank collectives
  ride the host plane only (the reference's model: 1 process = 1 GPU,
  ``README.md:62-64``).

Usage::

    python -m horovod_tpu.launcher -np 4 python examples/mnist.py
    tpurun -np 4 python train.py          # if bin/ on PATH
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

from ..utils.chips import chips_on_host, one_chip_env

# How long a worker gets between terminate() and kill() during teardown —
# enough for JAX runtimes to flush, short enough that a wedged worker
# cannot hold the job hostage.
TERMINATE_GRACE_SECS = 5.0

# After the FIRST worker failure, how long the siblings get to exit on
# their own before the supervisor terminates them. The coordination
# plane's ABORT reaches them within milliseconds and each then exits with
# the named WorkerFailureError — reaping instantly would race that and
# destroy the diagnosis; only ranks still alive after the grace (wedged,
# or not blocked in a collective) get the terminate→kill escalation.
FAILFAST_GRACE_SECS = 3.0


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _local_rank(index: int, *, cpu: bool, cpu_world: int) -> int:
    """Local rank of the ``index``-th process this host runs. On chips it
    names the process's OWN chip, so it never wraps: more processes than
    chips is refused (two ranks on one chip would fight over its lock)."""
    if cpu:
        return index % max(1, cpu_world)
    try:
        chips = chips_on_host()
    except RuntimeError as e:
        raise SystemExit(f"tpurun: {e}")
    if index >= chips:
        raise SystemExit(
            f"tpurun: process {index} of this host needs its own chip but "
            f"the host has {chips} (one process per chip; --cpu lifts "
            f"this for CPU worlds)")
    return index


def _rank_env(rank: int, local_rank: int, world: int, coord_addr: str, *,
              cpu: bool, extra_env: Optional[dict] = None,
              metrics_port: Optional[int] = None, restart_epoch: int = 0,
              resize_generation: int = 0,
              jd_addr: Optional[str] = None) -> dict:
    """Environment of one spawned rank: the process grid, the rendezvous
    address, and — on chips — exactly ONE chip, keyed by ``local_rank``."""
    env = dict(os.environ)
    env.update(extra_env or {})
    if metrics_port:
        # Each rank's obs listener binds metrics_port + rank
        # (horovod_tpu.obs.http); the flag is the launcher-side
        # spelling of HVD_METRICS_PORT.
        env["HVD_METRICS_PORT"] = str(metrics_port)
    env["HVD_RANK"] = str(rank)
    env["HVD_SIZE"] = str(world)
    env["HVD_LOCAL_RANK"] = str(local_rank)
    env["HVD_COORD_ADDR"] = coord_addr
    # Which (re)launch of the world this is; read by the elastic
    # recovery API and the fault injector's @epoch condition.
    env["HVD_RESTART_EPOCH"] = str(restart_epoch)
    if resize_generation:
        # Grow-spawned mid-resize: the rank joins the in-flight world
        # over the wire (elastic.resize_join) instead of restoring.
        env["HVD_RESIZE_GENERATION"] = str(resize_generation)
    if cpu:
        # CPU testing mode (reference CI: mpirun -np 2 on localhost
        # CPU-only, .travis.yml:84-91).
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env.update(one_chip_env(local_rank))
    if jd_addr:
        env["JAX_COORDINATOR_ADDRESS"] = jd_addr
        env["JAX_NUM_PROCESSES"] = str(world)
        env["JAX_PROCESS_ID"] = str(rank)
    return env


def _reap(procs: List[subprocess.Popen],
          grace_secs: float = TERMINATE_GRACE_SECS) -> None:
    """Terminate-then-kill every still-running worker, and REAP them all.

    terminate() alone is not cleanup: a worker blocked in a collective (or
    ignoring SIGTERM) survives it, and an unreaped child is a zombie
    holding its pipes open. Escalation: SIGTERM → wait up to
    ``grace_secs`` → SIGKILL → wait (SIGKILL cannot be ignored, so the
    final wait always returns).
    """
    for p in procs:
        if p.poll() is None:
            try:
                p.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + grace_secs
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
    for p in procs:
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
    for p in procs:
        try:
            p.wait()
        except OSError:
            pass


class _ResizeSupervisor:
    """Launcher-side state machine of the live-resize plane.

    Watches the coordinator's admin status (~2 RPCs/second of local TCP —
    nothing on the training hot path) so BOTH ingress forms work: resize
    signals delivered to tpurun itself (SIGUSR1 shrink / SIGUSR2 grow,
    the spot-preemption shape) and an operator's direct ``request_resize``
    RPC to the coordinator. On a pending grow it spawns the missing ranks
    wired to the NEW world's coordinator port; on a shrink it only reaps
    the retiring ranks' clean exits. When the OLD coordinator disappears
    (all old-world ranks re-formed), the supervisor follows the plane to
    the new port and updates its notion of the world — so a later crash
    restart relaunches the RESIZED world, and a later resize signal
    computes its target from the current size.
    """

    POLL_SECS = 0.5

    def __init__(self, coord_addr: str, world: int,
                 cap: Optional[int] = None, enabled: bool = True):
        self.coord_addr = coord_addr
        self.world = world
        self.initial_world = world
        self.cap = cap
        self.enabled = enabled
        self._seen_gen = 0
        self._pending: Optional[tuple] = None  # (target, port, generation)
        # Old plane observed down while quiescing: the resize is only
        # COMMITTED once the NEW world's coordinator answers — a job that
        # finishes (cleanly or not) in the same window must not be
        # misread as a successful re-form.
        self._confirming = False
        self._last_poll = 0.0
        # Ranks spawned for the CURRENT pending grow: they become real
        # world members when the resize commits; until then an abandoned
        # resize must reap them (they never joined anything — their
        # eventual connect-timeout exit is not a job failure).
        self._spawned: list = []
        self._reap: list = []

    def drain_reap(self) -> list:
        """Ranks whose spawned-but-never-joined processes the supervision
        loop must terminate and forget (filled by :meth:`abandon`)."""
        out, self._reap = self._reap, []
        return out

    def signal(self, signum: int) -> list:
        """Translate SIGUSR1/SIGUSR2 into an admin resize RPC. Returns
        grow spawns like :meth:`poll` (the RPC reply carries the pending
        triple, so the signal path never depends on winning a race with
        the quiescing world's teardown)."""
        if not self.enabled:
            sys.stderr.write(
                "tpurun: resize signal ignored — live resize supports "
                "single-node env-worlds (no --nnodes/--jax-distributed); "
                "use --restarts + the world-agnostic checkpoint to "
                "reshape such jobs\n")
            return []
        if self._pending is not None:
            sys.stderr.write(
                f"tpurun: resize signal ignored — resize to "
                f"{self._pending[0]} already in flight\n")
            return []
        if signum == signal.SIGUSR1:
            # Floor 2: a multi-process world cannot live-resize to a
            # single rank (the coordination plane needs >= 2; the
            # coordinator rejects target 1 with the -np 1 remedy).
            target = max(2, self.world // 2)
        else:
            cap = self.cap if self.cap is not None else self.initial_world
            # A grow signal must never shrink: a cap below the current
            # world (possible after operator RPC-driven grows) clamps the
            # grow to a no-op, not a downsize.
            target = max(self.world, min(max(cap, 1), self.world * 2))
        if target == self.world:
            sys.stderr.write(
                f"tpurun: resize signal is a no-op at world {self.world} "
                f"(shrink floor 2 / grow cap "
                f"{self.cap if self.cap is not None else self.initial_world}"
                f" — raise --max-np to grow further)\n")
            return []
        kind = "shrink" if target < self.world else "grow"
        sys.stderr.write(
            f"tpurun: {kind} signal — requesting live resize "
            f"{self.world} -> {target}\n")
        try:
            from ..coord.client import request_resize
            out = request_resize(self.coord_addr, target, timeout=5.0)
        except Exception as e:  # noqa: BLE001 — supervision must survive
            sys.stderr.write(
                f"tpurun: resize request failed ({e}); the world is "
                f"unchanged — retry once training is underway\n")
            return []
        return self._adopt(out.get("pending_target"), out.get("coord_port"),
                           out.get("generation"))

    def _adopt(self, target, port, gen) -> list:
        """Record a newly observed pending resize; returns the grow
        spawns (rank, generation, new-world coordinator address)."""
        if (not target or not port or gen is None
                or gen <= self._seen_gen or self._pending is not None):
            return []
        self._pending = (target, port, gen)
        host = self.coord_addr.partition(":")[0] or "127.0.0.1"
        sys.stderr.write(
            f"tpurun: live resize {self.world} -> {target} pending "
            f"(generation {gen}); supervising the re-form — no "
            f"restart\n")
        # Grow: spawn the missing ranks now, aimed at the NEW world's
        # coordinator; they come up while the old world quiesces.
        self._spawned = list(range(self.world, target))
        return [(r, target, gen, f"{host}:{port}")
                for r in range(self.world, target)]

    def target(self) -> int:
        """The world size being resized to (current world when idle)."""
        return self._pending[0] if self._pending else self.world

    def abandon(self, rc: int = 0) -> None:
        """The in-flight resize is dead (a rank failed, or the world
        finished first): keep the OLD world size — on a failure
        ``--restarts`` relaunches it and the quiesce recommit restores
        through the verified walk. Spawned-but-unjoined grow ranks are
        queued for reaping (:meth:`drain_reap`) so their connect-timeout
        exits cannot mislabel the run."""
        if self._pending is None:
            return
        target, _, gen = self._pending
        confirming = self._confirming
        self._pending = None
        self._confirming = False
        self._seen_gen = gen
        self._reap.extend(self._spawned)
        self._spawned = []
        if rc:
            sys.stderr.write(
                f"tpurun: live resize to {target} ABANDONED — a rank "
                f"died mid-resize (exit code {rc}); the world fails over "
                f"to the supervised-restart path (verified restore from "
                f"the quiesce recommit)\n")
        elif confirming:
            # The old plane went down and the job then finished before
            # the new coordinator could be probed: with a short enough
            # post-resize run the supervisor cannot tell "resized then
            # completed" from "completed before quiescing" — both are
            # clean ends; the ranks' own logs carry the truth.
            sys.stderr.write(
                f"tpurun: world exited while live resize to {target} "
                f"was in flight (job complete; no restart performed)\n")
        else:
            sys.stderr.write(
                f"tpurun: live resize to {target} abandoned — the world "
                f"exited before the quiesce boundary was reached\n")

    def retired(self, rank: int) -> bool:
        """Whether ``rank``'s clean exit is a shrink retirement (benign —
        reap and forget) rather than end-of-training."""
        return self.enabled and rank >= self.target()

    def poll(self, healthy: bool = True) -> list:
        """Advance the state machine; returns the grow spawns (usually
        empty). ``healthy`` is the supervision loop's view of the ranks
        that must SURVIVE the pending resize — an unreachable old
        coordinator only counts as "resize committed" while they are all
        alive; otherwise the world died mid-resize and ``--restarts``
        must relaunch the OLD world from the quiesce recommit."""
        if not self.enabled:
            return []
        now = time.monotonic()
        if now - self._last_poll < self.POLL_SECS and not self._confirming:
            # Confirming bypasses the poll gate: the re-formed world may
            # run only briefly (short jobs, drills) and the commit must be
            # observed inside that window.
            return []
        self._last_poll = now
        from ..coord.client import resize_status
        host = self.coord_addr.partition(":")[0] or "127.0.0.1"
        if self._pending is None:
            try:
                st = resize_status(self.coord_addr, timeout=2.0,
                                   supervisor=True)
            except Exception:  # noqa: BLE001 — not up yet / transitioning
                return []
            return self._adopt(st.get("pending_target"),
                               st.get("coord_port"), st.get("generation"))
        target, port, gen = self._pending
        if not healthy:
            self.abandon()
            return []
        if not self._confirming:
            try:
                resize_status(self.coord_addr, timeout=2.0,
                          supervisor=True)
                return []  # old plane still up: still quiescing
            except Exception:  # noqa: BLE001 — old coordinator gone
                # Either the ranks tore the old plane down to re-form, or
                # the job is exiting. Don't decide yet — confirm against
                # the NEW world's coordinator.
                self._confirming = True
                return []
        try:
            st = resize_status(f"{host}:{port}", timeout=2.0,
                               supervisor=True)
        except Exception:  # noqa: BLE001 — new world still forming
            return []
        if st.get("world") != target:
            return []  # not our coordinator (yet)
        # The NEW coordinator answers with the resized world: committed.
        self.world = target
        self.coord_addr = f"{host}:{port}"
        self._seen_gen = gen
        self._pending = None
        self._confirming = False
        self._spawned = []  # joiners are real world members now
        sys.stderr.write(
            f"tpurun: live resize to {target} committed "
            f"(coordinator now at {self.coord_addr}); surviving "
            f"ranks kept their processes — resize is not a restart\n")
        return []


def _fleet_poller(world: int, metrics_port: Optional[int],
                  interval: float, ranks=None):
    """Build the ``--metrics-summary`` fleet poller when a metrics base
    port is known (flag or inherited ``HVD_METRICS_PORT``); None
    otherwise. ``ranks`` restricts the scrape to this node's rank block
    on multi-host launches (remote ranks' listeners are not on this
    loopback). Imported lazily — the launcher must not pull the obs
    stack unless asked."""
    base = metrics_port
    if not base:
        try:
            base = int(os.environ.get("HVD_METRICS_PORT", "0") or 0)
        except ValueError:
            base = 0
    if not base:
        sys.stderr.write(
            "tpurun: --metrics-summary needs a metrics base port "
            "(--metrics-port or HVD_METRICS_PORT) — no fleet view\n")
        return None
    from ..obs.summary import FleetPoller
    return FleetPoller("127.0.0.1", base, world, timeout=max(
        0.2, min(2.0, interval / 2)), ranks=ranks)


def _launch_once(np_: int, command: List[str], *,
                 coord_port: Optional[int], jax_distributed: bool,
                 cpu: bool, node_rank: int, nnodes: int,
                 coordinator: Optional[str], extra_env: Optional[dict],
                 restart_epoch: int,
                 max_np: Optional[int] = None,
                 metrics_summary: bool = False,
                 metrics_port: Optional[int] = None,
                 metrics_interval: float = 10.0) -> "tuple[int, bool, int]":
    """One supervised world launch: spawn, watch ALL ranks, fail fast.

    The seed's wait loop blocked on workers in spawn order: rank 3 dying
    first went unnoticed until ranks 0-2 exited — which, pre-abort, they
    never did (the reference's dead-rank-hangs-MPI failure mode). Here the
    supervisor polls every worker; on the FIRST failure it tears the
    surviving siblings down (terminate → kill escalation) so the job exits
    nonzero within seconds, not never.

    Live resize (single-node env-worlds): SIGUSR1/SIGUSR2 on the launcher
    halve/double the world (spot-preemption-style shrink/grow), translated
    into the coordinator's admin RPC; the supervision loop also POLLS the
    coordinator's resize status, so an operator's direct
    ``request_resize`` RPC is honored too — on a grow the launcher spawns
    the missing ranks (wired to the NEW world's coordinator port), on a
    shrink it simply reaps the retiring ranks' clean exits. No process
    that survives a resize is ever torn down — resize is not a restart.
    Returns ``(rc, interrupted, final_world)`` so ``--restarts`` relaunches
    at the CURRENT world size.
    """
    world = nnodes * np_
    if coordinator:
        coord_host, _, cport = coordinator.partition(":")
        coord_addr = f"{coord_host}:{cport or 29521}"
        jd_addr = f"{coord_host}:{int(cport or 29521) + 1}"
    else:
        coord_addr = f"127.0.0.1:{coord_port or _free_port()}"
        jd_addr = f"127.0.0.1:{_free_port()}" if jax_distributed else None
    procs: dict = {}  # rank -> Popen (resize adds/retires entries)
    interrupted = {"sig": None}
    resize_sig = {"sig": None}

    def _forward(signum, frame):
        # Forward the launcher's own termination (Ctrl-C / SIGTERM from a
        # job scheduler) to every worker; the supervision loop then reaps
        # with the usual escalation.
        interrupted["sig"] = signum
        for p in procs.values():
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass

    def _resize_signal(signum, frame):
        # Spot-preemption-style resize request; translated to the admin
        # RPC by the supervision loop (not here — a signal handler must
        # not do socket IO).
        resize_sig["sig"] = signum

    old_term = signal.signal(signal.SIGTERM, _forward)
    old_int = signal.signal(signal.SIGINT, _forward)
    old_usr1 = signal.signal(signal.SIGUSR1, _resize_signal)
    old_usr2 = signal.signal(signal.SIGUSR2, _resize_signal)
    fleet_stop = None   # set below; the finally must see it even when
    fleet_world = {"w": world}   # the spawn loop raises first

    def _spawn(rank: int, index: int, cur_world: int, addr: str,
               cpu_world: int, resize_generation: int = 0):
        procs[rank] = subprocess.Popen(command, env=_rank_env(
            rank, _local_rank(index, cpu=cpu, cpu_world=cpu_world),
            cur_world, addr, cpu=cpu, extra_env=extra_env,
            metrics_port=metrics_port, restart_epoch=restart_epoch,
            resize_generation=resize_generation,
            jd_addr=jd_addr if jax_distributed else None))

    try:
        # Refuse more processes than chips before any rank is spawned.
        _local_rank(np_ - 1, cpu=cpu, cpu_world=np_)
        for index in range(np_):
            # Rank-block layout across nodes; the local rank is the
            # process's index on THIS node.
            _spawn(node_rank * np_ + index, index, world, coord_addr,
                   cpu_world=np_)

        # Supervision loop: any-order exit detection + resize supervision.
        resize = _ResizeSupervisor(
            coord_addr=coord_addr, world=world, cap=max_np,
            enabled=(nnodes == 1 and not jax_distributed))
        # --metrics-summary runs on its OWN daemon thread: a hung rank
        # listener (up to ranks × 2 s of blocking scrapes) must never
        # stall the 0.05 s fail-fast poll that tears dead worlds down.
        if metrics_summary:
            local_ranks = (None if nnodes == 1 else
                           range(node_rank * np_, (node_rank + 1) * np_))
            fleet = _fleet_poller(world, metrics_port, metrics_interval,
                                  ranks=local_ranks)
            if fleet is not None:
                import threading
                fleet_stop = threading.Event()

                def _fleet_loop():
                    fleet_stop.wait(min(metrics_interval, 2.0))
                    while not fleet_stop.is_set():
                        fleet.set_world(fleet_world["w"])
                        try:
                            sys.stderr.write(
                                f"tpurun: {fleet.line()}\n")
                        except Exception:  # noqa: BLE001 — telemetry
                            pass           # must never kill supervision
                        fleet_stop.wait(metrics_interval)

                threading.Thread(target=_fleet_loop, daemon=True,
                                 name="tpurun-fleet").start()
        rc = 0
        while True:
            running = 0
            for r, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    running += 1
                elif code == 0 and resize.retired(r):
                    # A rank retiring at a shrink boundary: clean exit,
                    # remove from supervision (its rank index may be
                    # re-spawned by a later grow).
                    p.wait()
                    del procs[r]
                    sys.stderr.write(
                        f"tpurun: rank {r} retired (live shrink to "
                        f"{resize.target()})\n")
                elif code and not rc:
                    rc = code
            if rc:
                # A rank failed: if a resize was in flight it is dead too
                # — say so (and keep the OLD world size) before the
                # supervision loop exits into teardown/relaunch.
                resize.abandon(rc)
            if rc or not running or interrupted["sig"] is not None:
                break
            spawn = []
            if resize_sig["sig"] is not None:
                sig, resize_sig["sig"] = resize_sig["sig"], None
                spawn.extend(resize.signal(sig))
            # Ranks that must survive the resize (all of them when idle):
            # their death turns "old coordinator unreachable" from
            # "resize committed" into "world failed mid-resize". (rc is
            # always 0 here — a nonzero rc abandons and breaks above —
            # this covers a death the scan has not coded yet.)
            healthy = all(
                p.poll() is None for r, p in procs.items()
                if r < resize.target())
            spawn.extend(resize.poll(healthy=healthy))
            for rank, target, gen, addr in spawn:
                sys.stderr.write(
                    f"tpurun: live grow — spawning rank {rank} into world "
                    f"{target} (generation {gen}, coordinator "
                    f"{addr})\n")
                _spawn(rank, rank, target, addr, cpu_world=target,
                       resize_generation=gen)
            for r in resize.drain_reap():
                # Spawned for a resize that was abandoned: never joined a
                # world, so terminate and forget — their connect-timeout
                # exit must not read as a job failure.
                p = procs.pop(r, None)
                if p is not None:
                    _reap([p])
            world = resize.world
            fleet_world["w"] = world
            time.sleep(0.05)
        if rc and running:
            # Let the world's own abort cascade surface the diagnosis
            # (WorkerFailureError naming the dead rank) before tearing the
            # survivors down.
            deadline = time.monotonic() + FAILFAST_GRACE_SECS
            while time.monotonic() < deadline and any(
                    p.poll() is None for p in procs.values()):
                time.sleep(0.05)
            running = sum(1 for p in procs.values() if p.poll() is None)
            if running:
                sys.stderr.write(
                    f"tpurun: a worker exited with code {rc}; terminating "
                    f"{running} surviving rank(s)\n")
        _reap(list(procs.values()))
        if not rc:
            for p in procs.values():
                if p.returncode and not rc:
                    rc = p.returncode
        if interrupted["sig"] is not None and not rc:
            rc = 128 + int(interrupted["sig"])
        # The interruption flag travels alongside rc: an operator's Ctrl-C
        # / scheduler SIGTERM must never be mistaken for a worker failure
        # (which --restarts would relaunch). The final PER-NODE rank count
        # travels too: a crash AFTER a live resize relaunches at the
        # resized world, not the original one. Resize is single-node only,
        # so on multi-node launches this is always the original np_ —
        # returning the GLOBAL world there would multiply the world on
        # every restart (launch() feeds it back as the next epoch's
        # per-node count).
        return rc, interrupted["sig"] is not None, \
            (world if nnodes == 1 else np_)
    finally:
        if fleet_stop is not None:
            fleet_stop.set()
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGUSR1, old_usr1)
        signal.signal(signal.SIGUSR2, old_usr2)
        _reap(list(procs.values()))


def launch(np_: int, command: List[str], *, coord_port: Optional[int] = None,
           jax_distributed: bool = False, cpu: bool = False,
           node_rank: int = 0, nnodes: int = 1,
           coordinator: Optional[str] = None,
           extra_env: Optional[dict] = None,
           restarts: int = 0,
           max_np: Optional[int] = None,
           metrics_summary: bool = False,
           metrics_port: Optional[int] = None,
           metrics_interval: float = 10.0) -> int:
    """Spawn ``np_`` local ranks of ``command`` with the world env wired up.

    Multi-host: run tpurun on every host with the same ``--coordinator
    host0:port`` and ``--nnodes N``, giving each host its ``--node-rank``
    (the role of ``mpirun -H host1:4,host2:4``, reference
    ``docs/running.md:15-45``). World size = nnodes · np_; this host's ranks
    are ``node_rank·np_ .. node_rank·np_+np_-1``.

    Fault tolerance: every launch is supervised — the first failing rank
    tears down its siblings so a dead rank can never hang the job (the
    reference's MPI world does exactly that). With ``restarts > 0`` a
    failed world is relaunched up to ``restarts`` times on a FRESH
    coordinator port (the dead coordinator's socket may linger in
    TIME_WAIT) with exponential backoff, exporting ``HVD_RESTART_EPOCH``
    so workers resume from their last committed
    :class:`horovod_tpu.elastic.ElasticState` — the Elastic-Horovod role.

    Returns the first nonzero exit code (0 if all succeeded).
    """
    import random
    rc = 0
    np_cur = np_
    # Restart backoff: exponential base, CAPPED (HVD_RESTART_BACKOFF_MAX
    # seconds, default 30 — under repeated preemption an unbounded 2^n
    # sleep quickly dwarfs the restart it delays) and JITTERED ±50% so a
    # fleet of preempted jobs does not relaunch in lockstep against the
    # same scheduler. The chosen delay is logged.
    try:
        backoff_cap = float(os.environ.get("HVD_RESTART_BACKOFF_MAX",
                                           "30") or 30)
    except ValueError:
        backoff_cap = 30.0
    backoff_cap = max(0.0, backoff_cap)
    for epoch in range(restarts + 1):
        # Restart on a fresh port: the explicit multi-host --coordinator
        # address is pinned by the operator (every host must agree), but a
        # local auto-picked port is never reused across epochs.
        rc, interrupted, np_cur = _launch_once(
            np_cur, command,
            coord_port=coord_port if epoch == 0 else None,
            jax_distributed=jax_distributed, cpu=cpu, node_rank=node_rank,
            nnodes=nnodes, coordinator=coordinator, extra_env=extra_env,
            restart_epoch=epoch, max_np=max_np,
            metrics_summary=metrics_summary, metrics_port=metrics_port,
            metrics_interval=metrics_interval)
        if interrupted:
            # Operator interruption (Ctrl-C / scheduler SIGTERM) is a
            # command to STOP, not a failure to retry — never relaunch.
            break
        if rc == 0 or epoch == restarts:
            break
        base = min(1.0 * (2 ** epoch), backoff_cap)
        backoff = min(backoff_cap, base * random.uniform(0.5, 1.5))
        sys.stderr.write(
            f"tpurun: world failed with exit code {rc} (restart epoch "
            f"{epoch}); relaunching {np_cur} rank(s) in {backoff:.1f}s "
            f"(base {base:.1f}s, jitter ±50%, cap {backoff_cap:.0f}s; "
            f"{restarts - epoch} restart(s) left)\n")
        time.sleep(backoff)
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tpurun",
        description="Launch N ranks of a training script on this host "
                    "(mpirun replacement; see docs/running.md parity).")
    parser.add_argument("-np", type=int, required=True,
                        help="number of ranks (processes) to spawn")
    parser.add_argument("--cpu", action="store_true",
                        help="force JAX CPU backend in ranks (CI/testing)")
    parser.add_argument("--jax-distributed", action="store_true",
                        help="also form a jax.distributed world so compiled "
                             "collectives span processes")
    parser.add_argument("--coord-port", type=int, default=None)
    parser.add_argument("--node-rank", type=int, default=0,
                        help="this host's index among --nnodes hosts")
    parser.add_argument("--nnodes", type=int, default=1,
                        help="total hosts in the job (world = nnodes * np)")
    parser.add_argument("--coordinator", default=None,
                        help="host0:port rendezvous shared by all hosts "
                             "(required when nnodes > 1)")
    parser.add_argument("--restarts", type=int, default=0,
                        help="relaunch the whole world up to N times after "
                             "a failure (fresh coordinator port, capped + "
                             "jittered exponential backoff "
                             "[HVD_RESTART_BACKOFF_MAX], HVD_RESTART_EPOCH "
                             "exported); pair with "
                             "horovod_tpu.elastic.run_with_recovery to "
                             "resume from the last committed state")
    parser.add_argument("--max-np", type=int, default=None,
                        help="grow ceiling for live resize: SIGUSR2 "
                             "doubles the world up to this many ranks "
                             "(default: the initial -np). A direct admin "
                             "RPC (coord.client.request_resize) is not "
                             "capped — the operator named an exact size")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="base port of the per-rank /metrics "
                             "listeners: rank r serves on port+r "
                             "(exports HVD_METRICS_PORT to every rank; "
                             "see docs/observability.md)")
    parser.add_argument("--metrics-summary", action="store_true",
                        help="scrape every rank's /metrics and print one "
                             "aggregated fleet line. With a command: "
                             "every --metrics-interval seconds while "
                             "supervising. WITHOUT a command: one shot "
                             "against an already-running job's ranks, "
                             "then exit (needs -np + --metrics-port or "
                             "HVD_METRICS_PORT). Pointed at a serving "
                             "fleet's /metrics port (-np 1), prints the "
                             "replica-centric fleet line instead")
    parser.add_argument("--metrics-interval", type=float, default=10.0,
                        help="seconds between fleet lines under "
                             "--metrics-summary (default 10)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the command to run, e.g. python train.py")
    args = parser.parse_args(argv)
    if args.metrics_interval <= 0:
        parser.error("--metrics-interval must be > 0")
    if args.metrics_summary and not args.command:
        # One-shot fleet view of a job launched elsewhere: scrape the N
        # rank listeners once, print the line, exit 0 when any rank
        # answered (an all-dead fleet is worth a nonzero exit — the
        # operator asked "how is it doing" and the answer is "it isn't").
        fleet = _fleet_poller(args.np, args.metrics_port,
                              args.metrics_interval)
        if fleet is None:
            return 2
        line = fleet.line()
        print(f"tpurun: {line}", flush=True)
        # Structured verdict, not prose-parsing: exit 1 only when NO
        # training rank answered. A serving-fleet scrape that answered
        # is a live endpoint whatever its replica count says — exit 0.
        return 0 if (fleet.last_mode == "serving"
                     or fleet.last_up > 0) else 1
    if not args.command:
        parser.error("no command given")
    if args.nnodes > 1 and not args.coordinator:
        parser.error("--nnodes > 1 requires --coordinator host0:port")
    if args.restarts < 0:
        parser.error("--restarts must be >= 0")
    if args.max_np is not None and args.max_np < args.np:
        parser.error("--max-np must be >= -np (it is the grow ceiling)")
    return launch(args.np, args.command, coord_port=args.coord_port,
                  jax_distributed=args.jax_distributed, cpu=args.cpu,
                  node_rank=args.node_rank, nnodes=args.nnodes,
                  coordinator=args.coordinator, restarts=args.restarts,
                  max_np=args.max_np,
                  metrics_summary=args.metrics_summary,
                  metrics_port=args.metrics_port,
                  metrics_interval=args.metrics_interval)


if __name__ == "__main__":
    sys.exit(main())
