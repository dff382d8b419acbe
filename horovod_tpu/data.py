"""Input pipeline utilities: dataset loading, per-rank sharding, prefetch.

The reference delegates input to TF's pipelines (its examples feed
feed-dicts or Keras generators; real MNIST/CIFAR arrive via Keras
downloads). A TPU framework needs the equivalent plumbing in-framework:

* :func:`load_dataset` — real arrays from disk when present
  (``HVD_DATA_DIR``/``data_dir`` with ``mnist.npz`` / ``cifar10.npz`` in
  the Keras archive layout), the in-wheel real ``digits`` set (scikit-learn,
  no download needed), or a deterministic learnable synthetic stand-in.
* :func:`shard_iterator` — applies :func:`horovod_tpu.training.shard_batch`
  to every batch (world-axis split in single-controller/jax.distributed
  mode, this rank's contiguous slice in env-world mode).
* :func:`prefetch_to_device` — a bounded background thread that stages the
  next ``size`` sharded batches onto the devices while the current step
  runs, overlapping host input work (decode/augment/transfer) with device
  compute. On TPU this is the difference between MXU-bound and input-bound
  steps.

Typical loop::

    for batch in prefetch_to_device(shard_iterator(host_batches()), 2):
        state, metrics = step(state, batch)
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
from typing import Any, Iterable, Iterator, Optional, Tuple

import numpy as np

from .training import shard_batch


# ---------------------------------------------------------------------------
# Dataset loading (real data when available; synthetic stand-in otherwise).
# ---------------------------------------------------------------------------

def _synthetic(n, shape, classes, seed):
    rng = np.random.RandomState(seed)
    # A learnable task: labels depend linearly on the input so loss
    # actually decreases (pure noise would plateau instantly).
    x = rng.randn(n, *shape).astype(np.float32)
    w = rng.randn(int(np.prod(shape)), classes).astype(np.float32)
    y = np.argmax(x.reshape(n, -1) @ w, axis=1).astype(np.int32)
    return x, y


def load_dataset(name: str, data_dir: Optional[str] = None,
                 n_train: int = 4096, n_test: int = 512) -> Tuple[
                     Tuple[np.ndarray, np.ndarray],
                     Tuple[np.ndarray, np.ndarray], dict]:
    """Load ``name`` in {"mnist", "cifar10", "digits"}.

    Returns ``((x_train, y_train), (x_test, y_test), info)`` with
    ``info = {"real": bool, "classes": int}``. Real data is used when
    available: ``<data_dir or $HVD_DATA_DIR>/<name>.npz`` in the Keras
    archive layout (x_train/y_train/x_test/y_test) for mnist/cifar10;
    ``digits`` is scikit-learn's real 8x8 handwritten-digit set shipped in
    the wheel (1,797 images — usable for convergence validation with zero
    network egress). Without real data, a deterministic learnable
    synthetic stand-in with the same shapes is returned (``real: False``)
    so examples still demonstrate the framework end to end (the part the
    reference's downloads provided).
    """
    d = data_dir or os.environ.get("HVD_DATA_DIR")
    info = {"real": False, "classes": 10}
    if name == "digits":
        try:
            from sklearn.datasets import load_digits
        except ImportError as e:  # optional dependency (extras: datasets)
            raise ImportError(
                "load_dataset('digits') needs scikit-learn (the real 8x8 "
                "digit images ship inside its wheel): pip install "
                "scikit-learn, or pip install horovod_tpu[datasets]"
            ) from e
        x, y = load_digits(return_X_y=True)
        x = (x.astype(np.float32) / 16.0).reshape(-1, 8, 8, 1)
        y = y.astype(np.int32)
        # Deterministic shuffle + 80/20 split (the set ships unshuffled,
        # grouped by writer).
        idx = np.random.RandomState(0).permutation(len(x))
        x, y = x[idx], y[idx]
        n = int(0.8 * len(x))
        info["real"] = True
        return (x[:n], y[:n]), (x[n:], y[n:]), info
    # npz datasets: name -> (x transform, synthetic stand-in shape).
    _npz = {
        "mnist": (lambda x: x.reshape(-1, 784), (784,)),
        "cifar10": (lambda x: x, (32, 32, 3)),
    }
    if name not in _npz:
        raise ValueError(f"unknown dataset {name!r} "
                         "(expected mnist/cifar10/digits)")
    x_tf, syn_shape = _npz[name]
    path = d and os.path.join(d, f"{name}.npz")
    if path and os.path.exists(path):
        with np.load(path) as f:
            info["real"] = True
            return ((x_tf(f["x_train"]).astype(np.float32) / 255.0,
                     f["y_train"].astype(np.int32).ravel()),
                    (x_tf(f["x_test"]).astype(np.float32) / 255.0,
                     f["y_test"].astype(np.int32).ravel()), info)
    return (_synthetic(n_train, syn_shape, 10, 0),
            _synthetic(n_test, syn_shape, 10, 1), info)


def shard_iterator(batches: Iterable, mesh: Optional[Any] = None) -> Iterator:
    """Yield each global host batch placed onto the world (leading axis
    split across ranks; see :func:`horovod_tpu.training.shard_batch`)."""
    for batch in batches:
        yield shard_batch(batch, mesh=mesh)


class _Sentinel:
    pass


_END = _Sentinel()


def prefetch_to_device(batches: Iterable, size: int = 2,
                       sharding: Optional[Any] = None) -> Iterator:
    """Iterate ``batches`` with a background thread staying ``size`` batches
    ahead. Exceptions in the source iterator re-raise at the consuming
    ``next()`` call. Abandoning the iterator early (a ``break``, a
    stop-at-step hook) stops the worker, releases its staged batches, and
    closes the source iterator — no thread or device memory outlives the
    consumer.

    ``sharding`` places each batch from the WORKER thread: pass a single
    ``NamedSharding`` (applied to every leaf — e.g. the world mesh's
    leading-axis split) or a pytree of shardings matching the batch. This
    is what makes the prefetch depth actually overlap H2D for sharded
    meshes — without it the source must yield already-placed batches, and
    a source built on a default single-device ``device_put`` serializes
    the transfer into the consuming ``next()``.

    The worker thread records two program spans per batch, always (no
    timeline needed; :mod:`horovod_tpu.utils.timeline`): ``input.source``
    around the source iterator's ``next`` and ``H2D`` around the placement
    and its completion, both with ``batch=<sequence number>``. The
    consuming ``next()`` stamps the same ``batch=`` (and ``queue_depth=``,
    the batches that were ready when it asked) on the span it is called
    inside, so the three join. ``hvd_input_queue_depth`` and
    ``hvd_h2d_bytes_total`` carry the same to ``/metrics``.
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    return _prefetch_gen(batches, size, sharding)


def _prefetch_gen(batches: Iterable, size: int,
                  sharding: Optional[Any] = None) -> Iterator:
    import jax

    from .obs.registry import registry
    from .utils import timeline as _tl

    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()
    m_depth = registry().gauge(
        "hvd_input_queue_depth",
        "Prefetched batches ready when the step loop last asked for one "
        "(0: the loop is waiting on the input thread)")
    m_h2d_bytes = registry().counter(
        "hvd_h2d_bytes_total",
        "Bytes the prefetch thread placed on the devices")

    def _place(b, seq):
        if sharding is None:
            return b
        with _tl.span(_tl.H2D, batch=seq):
            if isinstance(sharding, jax.sharding.Sharding):
                placed = jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, sharding), b)
            else:
                placed = jax.device_put(b, sharding)
            # Block HERE, on the worker thread: device_put only dispatches
            # the copy, so without this the H2D phase measures dispatch
            # (~0) and the input-bound attribution under-reports — and a
            # dequeued batch must already be device-resident for the
            # prefetch depth to mean completed transfers.
            jax.block_until_ready(placed)
        m_h2d_bytes.inc(sum(getattr(x, "nbytes", 0)
                            for x in jax.tree_util.tree_leaves(placed)))
        return placed

    def _put(item) -> bool:
        # Bounded put with a stop check: the consumer may vanish while the
        # queue is full; never block forever on any worker-side put.
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _fill():
        try:
            source = iter(batches)
            for seq in itertools.count():
                with _tl.span("input.source", batch=seq) as sp:
                    try:
                        b = next(source)
                    except StopIteration:
                        sp.drop()
                        break
                if not _put((seq, _place(b, seq))):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            _put(e)
            return
        _put(_END)

    t = threading.Thread(target=_fill, daemon=True)
    t.start()

    try:
        while True:
            depth = q.qsize()
            item = q.get()
            if isinstance(item, _Sentinel):
                return
            if isinstance(item, BaseException):
                raise item
            seq, batch = item
            m_depth.set(depth)
            _tl.annotate(batch=seq, queue_depth=depth)
            yield batch
    finally:
        stop.set()
        # Unblock a worker stuck in put() and drop staged batches.
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        t.join(timeout=5)
        close = getattr(batches, "close", None)
        if close is not None:
            close()
