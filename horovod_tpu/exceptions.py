"""Error classification.

Mirrors the reference's error surface:

* not-initialized errors from the C ABI (``mpi_ops.cc:1530-1536`` —
  ``CheckInitialized`` returns FailedPrecondition "Horovod has not been
  initialized").
* cross-rank mismatch errors produced by coordinator validation
  (``ConstructMPIResponse``, ``mpi_ops.cc:266-474``) which surface to the
  calling op as ``tf.errors.FailedPreconditionError``.
* transport/library failures (``MPI_CHECK``/``CUDA_CHECK``/``NCCL_CHECK``,
  ``mpi_ops.cc:535-572``) which surface as Unknown errors.
"""


class HorovodError(Exception):
    """Base class for all framework errors."""


class NotInitializedError(HorovodError):
    """Raised when the process API is used before ``init()``.

    Parity: ``mpi_ops.py:85-88`` raises ValueError('Horovod has not been
    initialized; use hvd.init().'); the C side returns -1
    (``mpi_ops.cc:1539-1566``).
    """

    def __init__(self, what: str = "Horovod-TPU"):
        super().__init__(
            f"{what} has not been initialized; use horovod_tpu.init()."
        )


class FailedPreconditionError(HorovodError):
    """Cross-rank inconsistency detected during collective negotiation.

    Parity: the ERROR response path of ``ConstructMPIResponse``
    (``mpi_ops.cc:266-474``) → ``PerformOperation`` ERROR branch
    (``mpi_ops.cc:1141-1148``) → TF FailedPreconditionError on every rank.
    """


class TransportError(HorovodError):
    """Failure in the host coordination transport (DCN/TCP plane).

    Parity: ``MPI_CHECK`` converting MPI failures to errors::Unknown
    (``mpi_ops.cc:535-546``).
    """


class WorkerFailureError(TransportError):
    """A rank (or the coordinator) died or went silent; the world aborted.

    Subclasses :class:`TransportError`: worker death is detected on the
    transport plane (socket close / missed heartbeats), and pre-existing
    ``except TransportError`` handlers must keep catching a dead rank —
    they just lose the per-rank diagnosis the subclass adds.

    Raised by every blocked or future coordination-plane call once the
    rank-0 coordinator broadcasts an ABORT — because a rank's socket
    closed without a clean shutdown (process crashed/killed) or a rank
    went silent past ``HVD_HEARTBEAT_TIMEOUT`` — or when this rank itself
    stops receiving heartbeat-acks from the coordinator. The message
    names the dead party.

    The reference has no analog: a dead rank hangs ``MPI_Allreduce``
    forever and ``CheckForStalledTensors`` only warns
    (``mpi_ops.cc:1153-1196``). Recovery: exit nonzero, let
    ``tpurun --restarts N`` relaunch the world, and resume from the last
    committed :class:`horovod_tpu.elastic.ElasticState`.
    """


class ReplicaTimeoutError(TransportError):
    """A subprocess serving replica did not answer within its transport
    timeout.

    Raised by :class:`horovod_tpu.serve.proc_replica.ProcReplicaClient`
    when an HTTP round trip to the child worker times out (connect or
    read). Deliberately a *distinct* class from generic transport
    failures: :meth:`horovod_tpu.serve.router.ReplicaHandle.load` maps
    any other stats-surface exception to the ``1 << 30`` busy sentinel
    (route around it and move on), but a TIMEOUT means the child may be
    hung — the handle marks itself suspect and runs an immediate
    liveness check so a wedged process is evicted within one poll
    instead of being dispatch-demoted forever.
    """


class ServerOverloadedError(HorovodError):
    """The inference server's admission queue is full.

    Raised synchronously by :meth:`horovod_tpu.serve.Engine.submit` when
    the bounded request queue is at capacity — the load-shedding half of
    the serving backpressure contract (:mod:`horovod_tpu.serve`). Callers
    should treat it as retryable after backoff (HTTP 503 semantics; the
    bundled HTTP front end maps it exactly there). The reference has no
    serving plane; this extends the classification the same way
    :class:`StalledError` extends the collective plane.
    """


class DeadlineExceededError(HorovodError):
    """A queued inference request's deadline expired before execution.

    Delivered through the request's future (never raised on the engine
    thread): the batcher drops expired requests at dequeue so a stale
    request cannot occupy a batch slot that an in-deadline request needs.
    Maps to HTTP 504 in the bundled front end.
    """


class ServerClosedError(HorovodError):
    """The inference server is shut down (or shutting down).

    Raised by ``submit`` after ``shutdown()`` began, and delivered to any
    still-pending futures when a shutdown is NOT a graceful drain
    (``shutdown(drain=False)``). Distinct from
    :class:`ServerOverloadedError` because it is terminal, not retryable.
    """


class PreemptedError(HorovodError):
    """A generation stream was evicted from its decode slot by a
    higher-priority admission and could not be resumed within the
    engine's preemption retry budget.

    Raised through the stream's handle by the
    :class:`horovod_tpu.serve.GenerationEngine` preemption plane with
    terminal reason ``preempted_exhausted`` — the scheduling analog of
    :class:`FailoverExhaustedError`: the eviction itself is invisible
    to a client (the engine captures the stream's envelope exactly like
    a replica-death failover and replays it bit-identically), so only a
    stream preempted MORE times than ``GenerationConfig.
    preempt_retries`` ever sees this error. Under a
    :class:`horovod_tpu.serve.FleetRouter` it is additionally a
    failover cause: the stranded envelope is re-dispatched to another
    replica before the budget verdict lands, so a preemption on one
    replica can complete on a quieter one.
    """


class FailoverExhaustedError(HorovodError):
    """A generation stream stranded by replica death could not be
    resumed anywhere: it failed on its retry budget's worth of replicas
    (or the replay itself failed terminally on every attempt).

    Delivered through the stream's handle by the
    :class:`horovod_tpu.serve.FleetRouter` failover plane — the
    serving-plane analog of exhausting ``tpurun --restarts``. Distinct
    from :class:`ServerOverloadedError` on purpose: overload means "the
    fleet is full, back off and retry"; this means "this STREAM died N
    times and the router refuses to retry-storm it" — counted separately
    (``hvd_failover_total{outcome="exhausted"}``) so a dashboard can
    tell load shedding from failover churn. The client must re-submit
    from scratch if it still wants the result.
    """


class CheckpointCorruptError(HorovodError):
    """A checkpoint's bytes do not match its integrity manifest.

    Raised by the verify half of the checkpoint integrity plane
    (:func:`horovod_tpu.parallel.checkpoint.verify_checkpoint`): every
    save writes a per-leaf checksum manifest alongside the bytes, and a
    restore that finds a truncated file, a flipped bit, or a
    structure/dtype/shape mismatch raises this instead of silently
    resuming from poisoned state. The message names the checkpoint path
    and the first offending leaf.

    The reference's resume scan trusts whatever directory listing it
    finds (``keras_imagenet_resnet50.py:47-56``) — a torn write from a
    killed rank restores as garbage. Here the elastic restore chain
    (:meth:`horovod_tpu.elastic.ElasticState.restore`) catches this and
    walks back to the newest checkpoint that DOES verify, so a corrupt
    newest checkpoint costs one restore attempt, not the run.
    """

    def __init__(self, path: str, detail: str = ""):
        self.path = path
        self.detail = detail
        msg = f"checkpoint {path} failed integrity verification"
        if detail:
            msg += f": {detail}"
        super().__init__(msg)


class CheckpointTimeoutError(HorovodError):
    """An async checkpoint write did not become durable within the
    caller's deadline.

    Raised by :meth:`horovod_tpu.trainer.AsyncCheckpointer.wait` when a
    ``timeout=`` is given and the background writer is still in flight
    when it expires — a hung filesystem (dead NFS mount, wedged object
    store) otherwise blocks the durability barrier forever. The write
    itself is NOT cancelled: the writer thread keeps going, and a later
    ``wait()`` observes whatever it eventually did (success or the
    re-raised error).
    """


class NonFiniteGradError(HorovodError):
    """Too many consecutive non-finite-gradient steps with no checkpoint
    to roll back to.

    The in-jit bad-step guard (``make_train_step(guard_nonfinite=True)``)
    skips the optimizer update whenever any replica's gradients carry a
    NaN/Inf, leaving params bit-unchanged. ``Trainer.fit`` counts
    consecutive skips; after ``HVD_MAX_BAD_STEPS`` of them it rolls back
    to the last verified elastic checkpoint — or, when no
    :class:`horovod_tpu.elastic.ElasticState` is attached, raises this:
    a persistent NaN source (bad data shard, broken loss scale, flaky
    chip) is not going to fix itself, and silently skipping forever
    would burn the reservation training nothing.
    """


class StalledError(HorovodError):
    """A collective waited past the hard stall deadline (strict mode).

    Enabled by ``HOROVOD_STALL_TIMEOUT=<seconds>`` (0 = off, the default):
    an eager collective whose response does not arrive within the deadline
    — e.g. because another rank never announced it — raises this instead
    of blocking forever. The reference only warns
    (``CheckForStalledTensors``, ``mpi_ops.cc:1153-1196``); the hard
    timeout is a TPU-era extension for fail-fast fleet jobs.
    """
