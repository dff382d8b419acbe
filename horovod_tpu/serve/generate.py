"""Continuous-batching autoregressive generation over the KV-cache model
layer (``parallel.transformer.prefill``/``decode_step``).

The single-shot :class:`~.engine.Engine` batches whole requests; a
generation workload cannot — requests finish at different times, and
per-request batching would idle every slot until the slowest stream ends.
This engine does Orca-style *iteration-level* scheduling over vLLM-style
slot-managed KV memory instead:

* **Slots, not batches.** The decode step always executes at the fixed
  ``[max_slots]`` shape — ONE compiled program regardless of occupancy —
  and requests join/leave the batch at every decode-step boundary. A new
  request prefills into a free slot while its neighbors are mid-stream;
  a finished request frees its slot without anyone else noticing. Slot
  rows are numerically independent (each row of every matmul / softmax /
  cache read-write depends only on that row), so a request's token stream
  is **bit-identical** whether it runs alone or joins a busy batch — the
  invariance contract tests/test_generate.py pins.
* **KV memory is a layout knob** (``GenerationConfig.kv_layout``):
  ``"contiguous"`` reserves ``max_len`` rows per slot (capacity bounded
  by worst-case length), ``"paged"`` carves the same bytes into a
  fixed-size block pool with per-slot block tables
  (:mod:`horovod_tpu.parallel.kv_blocks`) — a stream holds only the
  blocks it fills, "cache full" becomes "block pool empty", and
  admission tracks free BLOCKS next to free slots. ``prefix_reuse=True``
  additionally shares full block-aligned prompt prefixes copy-on-write
  across streams (a system prompt's K/V lives once). Streams stay
  bit-identical across all three configurations
  (tests/test_paged_kv.py).
* **Compile cache** (the PR-2 pattern): one AOT-compiled decode
  executable for the engine's (max_slots, max_len), plus one prefill
  executable per power-of-two prompt bucket; :meth:`GenerationEngine.
  warmup` pre-compiles and pre-executes all of them so no user request
  ever pays a compile.
* **Sampling is per-request and host-side**: greedy / temperature /
  top-k, each request seeded with its own ``numpy`` Generator so a
  stream is reproducible no matter what shares its batch.
* **Backpressure carries over from PR 2** unchanged: bounded admission
  queue (:class:`~horovod_tpu.exceptions.ServerOverloadedError` at the
  door), deadlines checked when a request is dequeued into a slot
  (:class:`~horovod_tpu.exceptions.DeadlineExceededError` through the
  handle), graceful drain on shutdown, ``/healthz`` readiness via
  :class:`~.engine.ReadinessMixin`.
* **Overload degrades fairly, not FIFO-unfairly**: admission into free
  decode slots is ordered by :class:`~.sched.FairScheduler` (weighted
  deficit round-robin over tenants, strict priority classes above it) —
  pure host-side data, zero new compiled programs. Per-tenant KV block
  budgets (``tenant_block_budgets``) make one tenant's
  ``blocks_exhausted`` reject only THAT tenant; a higher-priority
  admission that finds no slot or blocks may preempt-by-evict the
  lowest-priority stream, capturing its envelope exactly like a
  replica-death failover and replaying it bit-identically in place
  (terminal reason ``preempted_exhausted`` only past
  ``preempt_retries``).

The loop is one background thread: the decode step is a single
accelerator program, and one consumer keeps slot assignment and the
queue's FIFO semantics trivially correct (fairness reorders held
requests ACROSS tenants only; within a tenant, FIFO holds).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import queue as std_queue
import signal
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import (DeadlineExceededError, PreemptedError,
                          ServerClosedError, ServerOverloadedError)
from ..obs import flightrec
from ..testing import faults
from ..parallel.kv_blocks import (TRASH_BLOCK, BlockManager, blocks_for,
                                  init_paged_kv_cache,
                                  paged_chunked_prefill, paged_decode_step,
                                  paged_prefill, paged_verify_step,
                                  prefix_route_digest)
from ..parallel.transformer import (TransformerConfig, decode_step,
                                    init_kv_cache, prefill, verify_step)
from .adapters import AdapterRegistry
from .batcher import RequestQueue, bucket_for
from .engine import ReadinessMixin
from .metrics import ServeMetrics
from .sched import FairScheduler
from .spec import SpecConfig, accept_greedy, accept_sampled

_DEFAULT = object()    # "knob not passed" sentinel (None is a real value)


def prefill_buckets(max_len: int) -> Tuple[int, ...]:
    """Prompt-padding buckets: powers of two below ``max_len``, topped by
    ``max_len`` itself — so the compile cache is ``log2(max_len)+1``
    programs and every bucket fits the cache."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    sizes: List[int] = []
    b = 1
    while b < max_len:
        sizes.append(b)
        b *= 2
    sizes.append(max_len)
    return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs. ``temperature <= 0`` is greedy (argmax;
    ``top_k``/``seed`` ignored). ``top_k=0`` samples the full vocab.
    ``seed`` makes the stream reproducible: the request owns a private
    ``numpy`` Generator, so identical (prompt, params, seed) produce an
    identical stream regardless of what else shares the batch."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Engine knobs. ``max_slots`` is the decode batch width (the number
    of concurrently generating requests) and ``max_len`` the per-request
    cache depth cap (prompt + generated tokens). How much HBM that costs
    depends on ``kv_layout``:

    * ``"contiguous"`` reserves ``max_len`` rows per slot up front —
      ``2 · n_layers · max_slots · max_len · d_model`` cache elements,
      capacity bounded by the WORST-case sequence length.
    * ``"paged"`` allocates ``2 · n_layers · n_blocks · block_size ·
      d_model`` elements once and hands slots blocks as they fill them;
      a short stream holds only ``ceil(len/block_size)`` blocks, so the
      same bytes admit more concurrent short streams, and admission is
      bounded by free blocks (``blocks_exhausted``) as well as free
      slots (``slots_full``).

    ``block_size`` (paged) is the positions-per-block knob — a
    TPU-lane-friendly power of two; 16 default. ``n_blocks`` (paged)
    sizes the pool INCLUDING the reserved trash block; ``None`` matches
    the contiguous footprint (``max_slots · ceil(max_len/block_size) +
    1``). ``prefix_reuse`` (paged) shares full block-aligned prompt
    prefixes copy-on-write across streams. ``paged_kernel`` gathers
    decode attention through the Pallas paged kernel
    (``ops.pallas_paged_attention``) and the engine refuses at
    construction where that kernel cannot run (a real TPU needs
    ``d_head % 128 == 0``); off = the pure-lax gather path, the
    bit-identity reference.

    ``chunked_prefill`` (paged + prefix_reuse) switches EVERY admission
    to :func:`~horovod_tpu.parallel.kv_blocks.paged_chunked_prefill`: a
    prefix-hit admission compiles/executes a SUFFIX-sized program that
    reads the hit blocks' K/V out of the pool instead of recomputing
    them, and a cold admission is the same scan started at block 0 — so
    hit and cold streams stay bitwise identical (the chunked engine's
    bit-identity reference is ITSELF, not the non-chunked layouts; see
    the kv_blocks docstring). ``chunk_blocks`` is the scan's chunk width
    in blocks; ``max_len`` must hold at least two chunks and divide
    evenly by the chunk.

    ``host_blocks`` (paged + prefix_reuse) adds a host-memory tier of
    that many blocks: cold registered prefixes offload there instead of
    being dropped at reclaim, and an admission whose chain continues in
    the host tier kicks an async prefetch — the decode step NEVER
    blocks on a fetch. ``host_admission`` picks what that admission does
    meanwhile: ``"wait"`` holds it in the queue until the prefetch lands
    (FIFO preserved), ``"miss"`` admits immediately with the device-tier
    hits only (recompute, never a stale read).

    The multi-tenant scheduling policy (all host-side data — none of
    these knobs is a compile key): ``tenant_weights`` /
    ``tenant_priorities`` / ``tenant_slo_ttft_ms`` map tenant names
    ("base" included) to their fair-share weight (> 0, default 1),
    strict priority class (higher admits first and may preempt lower;
    default 0) and TTFT SLO target in ms (feeds the
    ``hvd_tenant_slo_*`` burn series). An attached
    :class:`~.adapters.AdapterRegistry` row's own weight/priority/SLO
    overrides these engine defaults per tenant. ``tenant_block_budgets``
    (paged only) caps how many KV pool blocks a tenant may hold — over
    budget, a tenant's admissions are rejected (``blocks_exhausted``
    with a ``retry_after_ms`` hint) or starved WITHOUT holding any
    other tenant's line, and the tenant offloads/reclaims its OWN
    coldest blocks first. ``preempt``/``preempt_retries`` gate
    preempt-by-evict: whether a higher-priority admission may evict the
    lowest-priority active stream, and how many evictions one stream
    survives before failing with terminal reason
    ``preempted_exhausted``.

    The rest mirrors :class:`~.engine.ServeConfig`'s backpressure
    contract."""

    max_slots: int = 8
    max_len: int = 512
    max_queue: int = 256
    default_deadline_ms: Optional[float] = None
    default_max_new_tokens: int = 64
    eos_id: Optional[int] = None
    kv_layout: str = "contiguous"
    block_size: int = 16
    n_blocks: Optional[int] = None
    prefix_reuse: bool = False
    paged_kernel: bool = False
    chunked_prefill: bool = False
    chunk_blocks: int = 1
    host_blocks: int = 0
    host_admission: str = "wait"
    tenant_weights: Optional[Dict[str, float]] = None
    tenant_priorities: Optional[Dict[str, int]] = None
    tenant_block_budgets: Optional[Dict[str, int]] = None
    tenant_slo_ttft_ms: Optional[Dict[str, float]] = None
    preempt: bool = True
    preempt_retries: int = 3

    def __post_init__(self):
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.default_max_new_tokens < 1:
            raise ValueError("default_max_new_tokens must be >= 1")
        if self.kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'contiguous' or 'paged', got "
                f"{self.kv_layout!r}")
        if self.block_size < 1 or (self.block_size & (self.block_size - 1)):
            raise ValueError(
                f"block_size must be a power of two, got {self.block_size}")
        if self.chunk_blocks < 1 or (self.chunk_blocks
                                     & (self.chunk_blocks - 1)):
            raise ValueError(
                f"chunk_blocks must be a power of two, got "
                f"{self.chunk_blocks}")
        if self.host_blocks < 0:
            raise ValueError(
                f"host_blocks must be >= 0, got {self.host_blocks}")
        if self.host_admission not in ("wait", "miss"):
            raise ValueError(
                f"host_admission must be 'wait' or 'miss', got "
                f"{self.host_admission!r}")
        if self.kv_layout != "paged":
            for knob in ("prefix_reuse", "paged_kernel", "chunked_prefill",
                         "host_blocks"):
                if getattr(self, knob):
                    raise ValueError(
                        f"{knob} requires kv_layout='paged'")
            if self.n_blocks is not None:
                raise ValueError(
                    "n_blocks applies to kv_layout='paged' only")
        elif self.n_blocks is not None and self.n_blocks < 2:
            raise ValueError(
                f"n_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {self.n_blocks}")
        if self.chunked_prefill:
            if not self.prefix_reuse:
                raise ValueError(
                    "chunked_prefill=True requires prefix_reuse=True "
                    "(its whole point is skipping prefix-hit compute)")
            c = self.chunk_tokens
            if self.max_len % c or self.max_len < 2 * c:
                raise ValueError(
                    f"chunked_prefill needs max_len divisible by the "
                    f"chunk ({self.chunk_blocks} blocks × "
                    f"{self.block_size} = {c} tokens) and at least two "
                    f"chunks, got max_len={self.max_len}")
        if self.host_blocks and not self.prefix_reuse:
            raise ValueError(
                "host_blocks > 0 requires prefix_reuse=True (only "
                "registered prefixes ever offload)")
        for t, w in (self.tenant_weights or {}).items():
            if w <= 0:
                raise ValueError(
                    f"tenant_weights[{t!r}] must be > 0, got {w} (use "
                    f"tenant_priorities, not zero weights, to de-class "
                    f"a tenant)")
        for t, s in (self.tenant_slo_ttft_ms or {}).items():
            if s <= 0:
                raise ValueError(
                    f"tenant_slo_ttft_ms[{t!r}] must be > 0, got {s}")
        if self.tenant_block_budgets:
            if self.kv_layout != "paged":
                raise ValueError(
                    "tenant_block_budgets requires kv_layout='paged' "
                    "(contiguous slots have no block pool to budget)")
            for t, b in self.tenant_block_budgets.items():
                if b < 1:
                    raise ValueError(
                        f"tenant_block_budgets[{t!r}] must be >= 1, "
                        f"got {b}")
        if self.preempt_retries < 0:
            raise ValueError(
                f"preempt_retries must be >= 0, got {self.preempt_retries}")

    @property
    def chunk_tokens(self) -> int:
        """Tokens per chunked-prefill scan trip."""
        return self.chunk_blocks * self.block_size

    @property
    def blocks_per_slot(self) -> int:
        """Blocks a full-depth (``max_len``) sequence occupies."""
        return blocks_for(self.max_len, self.block_size)

    @property
    def resolved_n_blocks(self) -> int:
        """``n_blocks`` with the default applied (contiguous-footprint
        pool + the trash block)."""
        if self.n_blocks is not None:
            return self.n_blocks
        return self.max_slots * self.blocks_per_slot + 1


class GenerationHandle:
    """Streaming result of one generation request.

    Consume incrementally (``for tok in handle: ...`` yields token ids as
    they are sampled; raises the failure exception if the request dies)
    or wait for completion: ``handle.result(timeout)`` returns
    ``{"tokens", "finish_reason" ("eos"|"length"), "n_tokens",
    "ttft_ms", "tokens_per_sec"}``. Both can be used together — the
    iterator drains a private event queue, ``result`` reads the
    accumulated state.
    """

    def __init__(self):
        self._events: std_queue.Queue = std_queue.Queue()
        self._done = threading.Event()
        self._tokens: List[int] = []
        self._error: Optional[BaseException] = None
        self._info: Optional[Dict] = None
        self.request: Any = None    # the engine's _GenRequest (debug/test)

    # -- engine side -------------------------------------------------------

    def _emit(self, tok: int) -> None:
        self._tokens.append(tok)
        self._events.put(("token", tok))

    def _finish(self, info: Dict) -> None:
        self._info = info
        self._done.set()
        self._events.put(("done", info))

    def _fail(self, exc: BaseException) -> None:
        if self._done.is_set():
            return
        self._error = exc
        self._done.set()
        self._events.put(("error", exc))

    # -- client side -------------------------------------------------------

    def next_event(self, timeout: Optional[float] = None):
        """``("token", id)`` / ``("done", info)`` / ``("error", exc)`` in
        emission order; raises ``queue.Empty`` on timeout."""
        return self._events.get(timeout=timeout)

    def __iter__(self):
        while True:
            kind, val = self._events.get()
            if kind == "token":
                yield val
            elif kind == "done":
                return
            else:
                raise val

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> Dict:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"generation not finished within {timeout} s")
        if self._error is not None:
            raise self._error
        return dict(self._info)


@dataclasses.dataclass
class _GenRequest:
    """One queued/in-flight generation request."""

    tokens: np.ndarray               # [L] int32 prompt
    max_new: int
    sampling: SamplingParams
    eos: Optional[int]
    handle: GenerationHandle
    enqueued_at: float               # time.monotonic()
    deadline_at: Optional[float]
    rng: np.random.Generator
    n_out: int = 0
    t_admit: Optional[float] = None     # dequeued into a slot
    t_first: Optional[float] = None     # first token sampled
    # Multi-tenant adapter identity: tenant is the quota/metrics key
    # ("base" for adapter-less traffic), adapter the registry name (None
    # = base), adapter_slot the table row the stream's adapter_idx pins
    # for its whole lifetime (resolved at submit, protected by the
    # registry refcount until _req_done releases it).
    tenant: str = "base"
    adapter: Optional[str] = None
    adapter_slot: int = -1
    # Engine-local stream id: the flight recorder's serving events
    # (admit/complete/crash) key on it, so a dead replica's post-mortem
    # can name exactly which streams were in flight.
    stream_id: int = -1
    # Prefix-reuse registry salt: a prompt's cached K/V is a function of
    # the weights that wrote it, so tenants must never hit each other's
    # prefixes (nor a reloaded adapter its predecessor's). Base traffic
    # carries the reserved NUL frame, NOT b"": adapter salts start with
    # a name character ([A-Za-z0-9], never NUL), so a base key can never
    # byte-equal an adapter key even when crafted token values spell an
    # adapter's salt — with an unframed b"" it could.
    prefix_salt: bytes = b"\x00"
    _done_accounted: bool = False
    # Speculation accounting (engine-filled when spec decoding is on):
    # drafts proposed for / accepted into this stream.
    spec_proposed: int = 0
    spec_accepted: int = 0
    # Priority class resolved at submit (registry row, else the
    # config map, else 0) — data the scheduler and the preemption
    # plane read; never a compile key.
    priority: int = 0
    # Preemption envelope (the engine-local analog of the fleet
    # failover replay): times this stream was evicted from its slot,
    # and — while resuming — the already-emitted prefix to regenerate
    # suppressed-and-verified before anything new reaches the handle.
    retries: int = 0
    replay_expect: Optional[List[int]] = None
    replay_i: int = 0
    # Held-line bookkeeping: whether this request holds a max_queue
    # admission ticket (False for preempted re-held streams — they were
    # admitted once already), and the host-tier prefetch keys it staged
    # (released if it expires while parked in the held line).
    held_ticket: bool = False
    prefetch_keys: set = dataclasses.field(default_factory=set)

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline_at is None:
            return False
        return (time.monotonic() if now is None else now) >= self.deadline_at

    def sample(self, logits: np.ndarray) -> int:
        t = self.sampling.temperature
        if t <= 0:
            return int(np.argmax(logits))
        x = logits.astype(np.float64) / float(t)
        k = self.sampling.top_k
        keep = None
        if k and k < x.size:
            keep = np.argpartition(x, -k)[-k:]
            x = x[keep]
        e = np.exp(x - np.max(x))
        p = e / e.sum()
        j = int(self.rng.choice(p.size, p=p))
        return int(keep[j]) if keep is not None else j

    def probs(self, logits: np.ndarray) -> np.ndarray:
        """Full-vocab probabilities under this request's temperature /
        top-k transform — the TARGET distribution :meth:`sample` draws
        from, as the speculative rejection rule needs it (an arbitrary
        draft token's probability must be addressable; outside top-k it
        is exactly 0, so off-support drafts always reject). Callers
        guarantee ``temperature > 0``."""
        t = self.sampling.temperature
        x = logits.astype(np.float64) / float(t)
        k = self.sampling.top_k
        if k and k < x.size:
            keep = np.argpartition(x, -k)[-k:]
            xk = x[keep]
            e = np.exp(xk - np.max(xk))
            p = np.zeros(x.size, np.float64)
            p[keep] = e / e.sum()
            return p
        e = np.exp(x - np.max(x))
        return e / e.sum()


class GenerationEngine(ReadinessMixin):
    """Continuous-batching generation server over one transformer.

    Args:
      params: the ``parallel.transformer`` param pytree — from
        ``init_params``, or ``restore_for_inference(..., dtype=)`` (plain
        fp32/bf16 leaves, or int8 :class:`~horovod_tpu.ops.quant.
        QuantizedTensor` leaves, dequantized inside the compiled forward).
        Pre-sharded global ``jax.Array`` leaves serve as laid out.
      model_cfg: the :class:`~horovod_tpu.parallel.transformer.
        TransformerConfig` the params belong to (dense FFN only).
      config: :class:`GenerationConfig`.
      adapters: optional :class:`~.adapters.AdapterRegistry` — the
        multi-tenant plane. With it, ``submit(adapter="name")`` serves
        that tenant's LoRA fine-tune: the per-slot ``adapter_idx``
        gathers the tenant's table row inside the SAME compiled
        prefill/decode programs (one compile cache whether the batch is
        base-only or mixed-adapter), per-tenant quotas gate admission,
        and ``/stats``/``/metrics`` split TTFT and tokens by tenant.
    """

    def __init__(self, params: Any, model_cfg: TransformerConfig,
                 config: GenerationConfig = GenerationConfig(), *,
                 adapters: Optional[AdapterRegistry] = None,
                 spec: Optional[SpecConfig] = None):
        if model_cfg.n_experts:
            raise NotImplementedError(
                "generation supports dense FFNs only (n_experts=0)")
        self._params = params
        self._model_cfg = model_cfg
        self._cfg = config
        self._adapters = adapters
        # Per-slot adapter table row, the decode program's gather index
        # (-1 = base). Data, not a compile key.
        self._adapter_idx = np.full((config.max_slots,), -1, np.int32)
        self._tenant_lock = threading.Lock()
        self._tenant_inflight: Dict[str, int] = {}
        self._queue = RequestQueue(config.max_queue)
        self._metrics = ServeMetrics()
        if adapters is not None:
            # Tenant churn must not grow per-tenant metric state without
            # bound: fold an evicted tenant's counters into "retired".
            adapters.add_evict_listener(self._metrics.forget_tenant)
        # Fair admission: WDRR over tenants + strict priority classes.
        # Weight/priority lookups go through the engine resolvers so a
        # registry set_weight/set_priority applies from the next pick.
        self._sched = FairScheduler(self._weight_of, self._priority_of)
        # Block DEMAND a tenant has in flight (reserved at the door,
        # freed at _req_done) — the budget's admission-time half; the
        # pool's owner ledger is the occupancy half. Under _tenant_lock.
        self._tenant_blocks: Dict[str, int] = {}
        self._paged = config.kv_layout == "paged"
        s = config.max_slots
        if self._paged:
            from ..ops.pallas_paged_attention import paged_attention_supported
            self._n_blocks = config.resolved_n_blocks
            self._cache = init_paged_kv_cache(
                model_cfg, self._n_blocks, config.block_size, s)
            self._blocks = BlockManager(self._n_blocks, config.block_size,
                                        host_blocks=config.host_blocks)
            for t, b in (config.tenant_block_budgets or {}).items():
                self._blocks.set_budget(t, int(b))
            max_blocks = config.blocks_per_slot
            self._tables = np.full((s, max_blocks), TRASH_BLOCK, np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(s)]
            d_head = model_cfg.d_model // model_cfg.n_heads
            self._use_kernel = bool(config.paged_kernel)
            if self._use_kernel and not paged_attention_supported(
                    d_head, config.block_size):
                # Asked for and cannot run: refuse — never serve the
                # gather path under the kernel's name.
                raise ValueError(
                    f"paged_kernel=True cannot run on backend "
                    f"{jax.default_backend()!r} at d_head={d_head} (the "
                    f"Pallas paged decode kernel needs d_head % 128 == "
                    f"0); set paged_kernel=False for the gather path")
        else:
            self._cache = init_kv_cache(model_cfg, s, config.max_len)
            self._blocks = None
        self._chunked = self._paged and config.chunked_prefill
        # Host-tier prefetch plumbing: entries staged by admission
        # attempts, APPLIED at the top of each loop iteration — the
        # decode step itself never waits on a host→device copy.
        self._host_cap = config.host_blocks if self._paged else 0
        self._prefetch_q: deque = deque()
        self._prefetch_inflight: set = set()
        self._last_prefill_bucket: Optional[int] = None
        # Speculative decoding plane (spec.py): draft k tokens host-side,
        # verify k+1 positions in one compiled program, accept per slot.
        # An optimization, never a liveness dependency — a step with no
        # drafts anywhere is exactly the plain decode program.
        self._spec = spec
        if spec is not None:
            if spec.k + 1 > config.max_len:
                raise ValueError(
                    f"spec k={spec.k} needs k+1 <= max_len="
                    f"{config.max_len}")
            if self._paged and self._use_kernel:
                # The Pallas decode kernel is allclose- (not bitwise-)
                # pinned against the gather path; mixing it with the
                # gather-based verify would break the greedy
                # spec-on == spec-off digest contract mid-stream.
                raise ValueError(
                    "speculative decoding requires the gather decode "
                    "path; set paged_kernel=False")
            self._drafter = spec.make_drafter()
        self._buckets = prefill_buckets(config.max_len)
        # Chunked buckets are the SAME power-of-two grid restricted to
        # multiples of the chunk holding >= 2 chunks (the scan-unroll
        # floor), so the compile-cache count stays bounded by the grid.
        c = config.chunk_tokens
        self._chunked_buckets = tuple(
            b for b in self._buckets if b % c == 0 and b >= 2 * c)
        # Requests popped from the admission queue but not yet in a slot
        # (the paged layout can be slot-free but block-starved; FIFO is
        # preserved — a head request short on blocks holds the line).
        self._held: deque = deque()
        self._peak_active = 0
        self._slots: List[Optional[_GenRequest]] = [None] * s
        self._positions = np.full((s,), -1, np.int32)
        self._last = np.zeros((s,), np.int32)
        self._compiled: Dict[Any, Any] = {}
        self._compile_lock = threading.Lock()
        # Mirrored under a micro-lock so stats() never waits on a compile
        # (same reasoning as Engine._compiled_ids).
        self._compiled_ids: set = set()
        self._stats_lock = threading.Lock()
        self._closed = False
        self._warmed = False
        self._abort = False
        # Serving-plane identity + liveness surface. ``serve_name`` is
        # stamped by the fleet router at attach (fault clauses and
        # flight-recorder events name replicas by it); the loop beat +
        # admitted-stream counter feed loop_alive() and the fault hook.
        self.serve_name = "engine"
        self._beat = time.monotonic()
        self._stall_mark: Optional[Tuple[float, float]] = None
        self._streams_started = 0
        self._loop_error_dumped = False
        self._stream_seq = itertools.count()
        self._thread = threading.Thread(target=self._loop,
                                        name="hvd-generate-loop",
                                        daemon=True)
        self._thread.start()

    def loop_alive(self, stall_s: float = 60.0) -> bool:
        """The in-process liveness probe a :class:`~.router.
        ReplicaHandle` polls for thread replicas: False once this
        engine's loop thread died without a shutdown (abrupt death —
        the ``replica_kill`` drill shape), or once the loop has been
        OBSERVED with work pending (live slots, held or queued
        requests) and no completed iteration for ``stall_s`` seconds
        (a wedged loop — the ``replica_hang`` drill shape). The stall
        clock starts at the first busy observation with no progress
        since, NOT at the raw loop-beat age: an IDLE loop parks in the
        untimed queue wait by design, so its beat is legitimately
        stale — a request landing in that queue must not read as a
        wedge before the loop has had ``stall_s`` to wake. ``stall_s``
        must still cover the engine's worst single iteration — a lazy
        first-bucket compile can legitimately hold the loop for tens
        of seconds on CPU."""
        if self._closed:
            return True     # a drained/shut-down loop exit is not death
        if not self._thread.is_alive():
            return False
        if not stall_s:
            return True
        busy = (any(r is not None for r in self._slots)
                or self._held or len(self._queue))
        now = time.monotonic()
        if not busy:
            self._stall_mark = None
            return True
        mark = self._stall_mark
        if mark is not None and self._beat != mark[1]:
            mark = None     # the loop iterated since the last mark
        if mark is None:
            self._stall_mark = (now, self._beat)
            return True
        return now - mark[0] <= stall_s

    # -- compile cache -----------------------------------------------------

    def _sds(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.asarray(x).dtype
                                           if not hasattr(x, "dtype")
                                           else x.dtype), tree)

    def _compile(self, key):
        """AOT-compile the ``key`` executable (idempotent): ``"decode"``
        or ``("prefill", bucket)``."""
        exe = self._compiled.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._compiled.get(key)
            if exe is None:
                cfg = self._model_cfg
                s = self._cfg.max_slots
                paged = self._paged
                has_ad = self._adapters is not None
                lcfg = self._adapters.lora if has_ad else None
                p_sds = self._sds(self._params)
                c_sds = self._sds(self._cache)
                a_sds = (self._sds(self._adapters.table())
                         if has_ad else None)
                i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
                nb = self._cfg.blocks_per_slot
                # One signature rule for every variant (adapter table
                # right after params, adapter_idx right after the last
                # scalar/positions, paged row/tables last) — the arg
                # builders below (_decode_args/_prefill_args/warmup)
                # follow the same rule, so adapter-enabled engines keep
                # the compile-cache KEYS (and count) of base-only ones.
                if key == "decode":
                    kern = self._use_kernel if paged else False

                    def _decode(*a):
                        it = iter(a)
                        p = next(it)
                        at = next(it) if has_ad else None
                        toks, c, pos = next(it), next(it), next(it)
                        aidx = next(it) if has_ad else None
                        if paged:
                            return paged_decode_step(
                                p, toks, c, pos, next(it), cfg,
                                kernel=kern, adapters=at,
                                adapter_idx=aidx, lora=lcfg)
                        return decode_step(p, toks, c, pos, cfg,
                                           adapters=at, adapter_idx=aidx,
                                           lora=lcfg)
                    sds = ([p_sds] + ([a_sds] if has_ad else [])
                           + [i32(s), c_sds, i32(s)]
                           + ([i32(s)] if has_ad else [])
                           + ([i32(s, nb)] if paged else []))
                    exe = jax.jit(_decode).lower(*sds).compile()
                elif isinstance(key, tuple) and key[0] == "verify":
                    w = key[1]    # k + 1 positions per slot

                    def _verify(*a):
                        it = iter(a)
                        p = next(it)
                        at = next(it) if has_ad else None
                        toks, c, pos = next(it), next(it), next(it)
                        aidx = next(it) if has_ad else None
                        if paged:
                            return paged_verify_step(
                                p, toks, c, pos, next(it), cfg,
                                adapters=at, adapter_idx=aidx, lora=lcfg)
                        return verify_step(p, toks, c, pos, cfg,
                                           adapters=at, adapter_idx=aidx,
                                           lora=lcfg)
                    # Same signature rule as "decode" — only the token
                    # operand widens to [S, W]. Exactly ONE verify
                    # executable per engine (one k), the compile-cache
                    # pin tests/test_spec.py holds.
                    sds = ([p_sds] + ([a_sds] if has_ad else [])
                           + [i32(s, w), c_sds, i32(s)]
                           + ([i32(s)] if has_ad else [])
                           + ([i32(s, nb)] if paged else []))
                    exe = jax.jit(_verify).lower(*sds).compile()
                elif (isinstance(key, tuple)
                        and key[0] == "chunked_prefill"):
                    t = key[1]    # bucket width (multiple of the chunk)
                    cb = self._cfg.chunk_blocks
                    ct = self._cfg.chunk_tokens

                    def _chunked(*a):
                        it = iter(a)
                        p = next(it)
                        at = next(it) if has_ad else None
                        toks, c, slot, length, start = (
                            next(it), next(it), next(it), next(it),
                            next(it))
                        aidx = next(it) if has_ad else None
                        wrows, rrow = next(it), next(it)
                        c2, logits = paged_chunked_prefill(
                            p, toks, c, slot, wrows, rrow, start, cfg,
                            length=length, chunk_blocks=cb, adapters=at,
                            adapter_idx=aidx, lora=lcfg)
                        # Only the sampled row crosses back: the row
                        # scoring the LAST prompt position, which sits
                        # at suffix offset length - start - 1.
                        return c2, logits[length - start - 1]
                    # Same signature rule; the prefill scalars widen to
                    # (slot, length, start) and the paged tail carries
                    # the per-chunk write rows next to the read row.
                    sds = ([p_sds] + ([a_sds] if has_ad else [])
                           + [i32(t), c_sds, i32(), i32(), i32()]
                           + ([i32()] if has_ad else [])
                           + [i32(t // ct, cb), i32(nb)])
                    exe = jax.jit(_chunked).lower(*sds).compile()
                else:
                    t = key[1]

                    def _prefill(*a):
                        it = iter(a)
                        p = next(it)
                        at = next(it) if has_ad else None
                        toks, c, slot, length = (next(it), next(it),
                                                 next(it), next(it))
                        aidx = next(it) if has_ad else None
                        if paged:
                            c2, logits = paged_prefill(
                                p, toks, c, slot, next(it), cfg,
                                length=length, adapters=at,
                                adapter_idx=aidx, lora=lcfg)
                        else:
                            c2, logits = prefill(
                                p, toks, c, slot, cfg, length=length,
                                adapters=at, adapter_idx=aidx, lora=lcfg)
                        # Only the sampled row crosses back to the host —
                        # [vocab], not [T, vocab].
                        return c2, logits[length - 1]
                    sds = ([p_sds] + ([a_sds] if has_ad else [])
                           + [i32(t), c_sds, i32(), i32()]
                           + ([i32()] if has_ad else [])
                           + ([i32(nb)] if paged else []))
                    exe = jax.jit(_prefill).lower(*sds).compile()
                self._compiled[key] = exe
                with self._stats_lock:
                    self._compiled_ids.add(
                        key if key == "decode" else f"{key[0]}_{key[1]}")
        return exe

    def warmup(self) -> Tuple[Any, ...]:
        """Pre-compile AND pre-execute the decode step and every prefill
        bucket before traffic (the cache is functional state — warmup
        outputs are discarded, so it stays pristine). Returns the keys
        warmed."""
        s = self._cfg.max_slots
        nb = self._cfg.blocks_per_slot
        has_ad = self._adapters is not None
        # All-trash tables/rows and all-base (-1) adapter indices:
        # warmup scratch lands in the reserved block, pool and adapter
        # table stay pristine.
        args = [self._params]
        if has_ad:
            args.append(self._adapters.table())
        args += [np.zeros((s,), np.int32), self._cache,
                 np.full((s,), -1, np.int32)]
        if has_ad:
            args.append(np.full((s,), -1, np.int32))
        if self._paged:
            args.append(np.full((s, nb), TRASH_BLOCK, np.int32))
        out = self._compile("decode")(*args)
        jax.block_until_ready(out)
        spec_keys: Tuple[Any, ...] = ()
        if self._spec is not None:
            w = self._spec.k + 1
            args = [self._params]
            if has_ad:
                args.append(self._adapters.table())
            args += [np.zeros((s, w), np.int32), self._cache,
                     np.full((s,), -1, np.int32)]
            if has_ad:
                args.append(np.full((s,), -1, np.int32))
            if self._paged:
                args.append(np.full((s, nb), TRASH_BLOCK, np.int32))
            out = self._compile(("verify", w))(*args)
            jax.block_until_ready(out)
            spec_keys = (("verify", w),)
        if self._chunked:
            # A chunked engine never compiles the plain prefill — every
            # admission (cold or hit) runs the chunked program, so only
            # the chunked bucket grid is warmed.
            ct = self._cfg.chunk_tokens
            for t in self._chunked_buckets:
                args = [self._params]
                if has_ad:
                    args.append(self._adapters.table())
                args += [np.zeros((t,), np.int32), self._cache,
                         np.asarray(0, np.int32), np.asarray(1, np.int32),
                         np.asarray(0, np.int32)]
                if has_ad:
                    args.append(np.asarray(-1, np.int32))
                args += [np.full((t // ct, self._cfg.chunk_blocks),
                                 TRASH_BLOCK, np.int32),
                         np.full((nb,), TRASH_BLOCK, np.int32)]
                out = self._compile(("chunked_prefill", t))(*args)
                jax.block_until_ready(out)
            self._warmed = True
            return ("decode",) + spec_keys + tuple(self._chunked_buckets)
        for t in self._buckets:
            args = [self._params]
            if has_ad:
                args.append(self._adapters.table())
            args += [np.zeros((t,), np.int32), self._cache,
                     np.asarray(0, np.int32), np.asarray(1, np.int32)]
            if has_ad:
                args.append(np.asarray(-1, np.int32))
            if self._paged:
                args.append(np.full((nb,), TRASH_BLOCK, np.int32))
            out = self._compile(("prefill", t))(*args)
            jax.block_until_ready(out)
        self._warmed = True
        return ("decode",) + spec_keys + tuple(self._buckets)

    # -- client API --------------------------------------------------------

    def submit(self, tokens: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               eos_id: Any = _DEFAULT,
               deadline_ms: Optional[float] = None,
               adapter: Optional[str] = None) -> GenerationHandle:
        """Enqueue one prompt; returns a :class:`GenerationHandle`
        streaming the sampled tokens. Raises
        :class:`ServerOverloadedError` when the admission queue is full
        (or the tenant is over quota — reason ``tenant_quota``),
        :class:`ServerClosedError` after shutdown, ``ValueError`` on a
        malformed or cache-overflowing prompt, on an ``adapter`` that is
        not resident, or on an ``adapter`` without a registry (all
        eagerly, in the caller's thread).

        ``max_new_tokens`` is clamped to the cache room left after the
        prompt (the stream then finishes with reason ``"length"``);
        ``eos_id=None`` disables EOS for this request even when the
        engine has a default. ``adapter`` names the tenant's resident
        LoRA fine-tune (None = base model); the stream pins the
        adapter's table row for its whole lifetime, so an evict racing
        a live stream is refused by the registry.
        """
        toks = np.asarray(tokens, np.int32)
        if toks.ndim != 1 or toks.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D int sequence, got shape "
                f"{toks.shape}")
        if toks.size > self._cfg.max_len:
            raise ValueError(
                f"prompt of {toks.size} tokens exceeds max_len="
                f"{self._cfg.max_len} (prompt + generated tokens share "
                f"the KV cache)")
        max_new = (self._cfg.default_max_new_tokens
                   if max_new_tokens is None else int(max_new_tokens))
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        # Token t+1's K/V lands at position L+t; the last sampled token
        # needs no cache write, so room caps new tokens at max_len-L+1.
        max_new = min(max_new, self._cfg.max_len - toks.size + 1)
        need_blocks = 0
        if self._paged:
            need_blocks = need = self._blocks_needed(toks.size, max_new)
            if need > self._blocks.usable:
                raise ValueError(
                    f"request needs {need} KV blocks (prompt "
                    f"{toks.size} + up to {max_new} generated, "
                    f"block_size={self._cfg.block_size}) but the pool "
                    f"holds only {self._blocks.usable} usable blocks — "
                    f"raise n_blocks or lower max_new_tokens")
        sampling = SamplingParams() if sampling is None else sampling
        eos = self._cfg.eos_id if eos_id is _DEFAULT else eos_id
        if deadline_ms is None:
            deadline_ms = self._cfg.default_deadline_ms
        tenant = "base" if adapter is None else adapter
        a_slot = -1
        salt = b"\x00"      # base frame — see _GenRequest.prefix_salt
        if adapter is not None:
            if self._adapters is None:
                raise ValueError(
                    f"submit(adapter={adapter!r}) on an engine without an "
                    f"AdapterRegistry — pass adapters= to "
                    f"GenerationEngine")
            # Retain BEFORE admission: the row must survive the queue
            # wait too (an evict of a queued tenant would otherwise free
            # the row its prefill is about to gather from).
            a_slot = self._adapters.retain(adapter)   # ValueError if absent
            # Generation read AFTER retain: the refcount blocks reloads,
            # so the salt is stable for the stream's whole lifetime.
            salt = (f"{adapter}\x00"
                    f"{self._adapters.generation(adapter)}\x00".encode())
        try:
            # Raises over-quota (tenant_quota) or over-block-budget
            # (blocks_exhausted) — both with a retry_after_ms hint.
            self._tenant_admit(tenant, need_blocks=need_blocks)
            now = time.monotonic()
            handle = GenerationHandle()
            req = _GenRequest(
                tokens=toks, max_new=max_new, sampling=sampling, eos=eos,
                handle=handle, enqueued_at=now,
                deadline_at=(None if deadline_ms is None
                             else now + deadline_ms / 1e3),
                rng=np.random.default_rng(sampling.seed),
                tenant=tenant, adapter=adapter, adapter_slot=a_slot,
                prefix_salt=salt, stream_id=next(self._stream_seq),
                priority=self._priority_of(tenant))
            handle.request = req
            try:
                depth = self._queue.put(req)   # raises Closed / Overloaded
            except ServerOverloadedError:
                self._tenant_release(tenant, blocks=need_blocks)
                reason, detail = self._overload_reason(toks.size, max_new)
                self._metrics.on_overload(reason)
                err = ServerOverloadedError(
                    f"request queue full ({self._cfg.max_queue}); "
                    f"{reason}: {detail}")
                # Backoff hint for the 503: how long until this queue
                # has drained at the engine's measured service rate.
                err.retry_after_ms = self._metrics.retry_after_ms(
                    len(self._queue))
                raise err from None
            except ServerClosedError:
                self._tenant_release(tenant, blocks=need_blocks)
                raise
        except BaseException:
            if adapter is not None:
                self._adapters.release(adapter)
            raise
        self._metrics.on_submit(depth)
        flightrec.record("serve_admit", replica=self.serve_name,
                         stream=req.stream_id, tenant=tenant,
                         prompt_len=int(toks.size))
        return handle

    def _tenant_admit(self, tenant: str, need_blocks: int = 0) -> None:
        """Count ``tenant``'s in-flight streams (queued + decoding) and
        reject over quota — atomically, so two racing submits cannot
        both squeeze under the cap. The rejection is its own reason
        (``tenant_quota``) next to ``slots_full``/``blocks_exhausted``:
        raising max_slots when one tenant is quota-bound fixes nothing.

        With a per-tenant block budget, ``need_blocks`` is additionally
        reserved against it HERE (released at :meth:`_req_done`): a
        tenant whose in-flight demand would exceed its budget is
        rejected at the door with reason ``blocks_exhausted`` — only
        THAT tenant's admissions, never another's, and with the same
        ``retry_after_ms`` backoff hint fleet 503s carry."""
        quota = (self._adapters.quota(tenant)
                 if self._adapters is not None else None)
        budget = self._blocks.budget(tenant) if self._paged else None
        if budget is not None and need_blocks > budget:
            raise ValueError(
                f"request needs {need_blocks} KV blocks but tenant "
                f"{tenant!r} has a block budget of {budget} — it can "
                f"NEVER be admitted; raise the tenant's budget or lower "
                f"max_new_tokens")
        with self._tenant_lock:
            inflight = self._tenant_inflight.get(tenant, 0)
            if quota is not None and inflight >= quota:
                self._metrics.on_overload("tenant_quota")
                err = ServerOverloadedError(
                    f"tenant {tenant!r} over quota: {inflight} streams "
                    f"in flight >= quota {quota} — finish streams or "
                    f"raise the tenant's quota")
                err.retry_after_ms = self._metrics.retry_after_ms(inflight)
                raise err
            if budget is not None:
                demand = self._tenant_blocks.get(tenant, 0)
                if demand + need_blocks > budget:
                    self._metrics.on_overload("blocks_exhausted")
                    err = ServerOverloadedError(
                        f"tenant {tenant!r} over KV block budget: "
                        f"{demand} blocks reserved in flight + "
                        f"{need_blocks} needed > budget {budget} — "
                        f"blocks_exhausted for THIS tenant only; finish "
                        f"streams or raise tenant_block_budgets")
                    err.retry_after_ms = self._metrics.retry_after_ms(
                        len(self._queue))
                    raise err
                self._tenant_blocks[tenant] = demand + need_blocks
            self._tenant_inflight[tenant] = inflight + 1

    def _tenant_label(self, req: _GenRequest) -> Optional[str]:
        """The tenant stamped into metrics: only multi-tenant engines
        (an AdapterRegistry attached) split by tenant — a base-only
        engine must not grow ``hvd_tenant_*{tenant="base"}`` series or
        a ``tenants`` /stats block it has no multi-tenant plane for."""
        return req.tenant if self._adapters is not None else None

    def _tenant_release(self, tenant: str, blocks: int = 0) -> None:
        with self._tenant_lock:
            n = self._tenant_inflight.get(tenant, 1) - 1
            if n > 0:
                self._tenant_inflight[tenant] = n
            else:
                self._tenant_inflight.pop(tenant, None)
            if blocks:
                d = self._tenant_blocks.get(tenant, 0) - blocks
                if d > 0:
                    self._tenant_blocks[tenant] = d
                else:
                    self._tenant_blocks.pop(tenant, None)

    def _req_done(self, req: _GenRequest) -> None:
        """One request left the system (finished, failed, expired or
        cancelled) — the single choke point for the tenant accounting:
        drop its in-flight count, its block-budget demand and its
        adapter-row reference.
        Idempotent (a drain timeout can walk the same request twice)."""
        if req._done_accounted:
            return
        req._done_accounted = True
        self._tenant_release(req.tenant, blocks=self._demand_of(req))
        if req.adapter is not None and self._adapters is not None:
            self._adapters.release(req.adapter)

    def _demand_of(self, req: _GenRequest) -> int:
        """The block demand :meth:`_tenant_admit` reserved for ``req``
        (0 when its tenant has no budget) — recomputed, not stored:
        deterministic in (prompt length, clamped max_new)."""
        if not self._paged or self._blocks.budget(req.tenant) is None:
            return 0
        return self._blocks_needed(req.tokens.size, req.max_new)

    # -- scheduling policy resolution ---------------------------------------
    # Registry row first (hot-settable per tenant), engine config map
    # second, neutral default last. Consulted at every pick/admission,
    # so policy changes apply at the next decode-step boundary.

    def _weight_of(self, tenant: str) -> float:
        if self._adapters is not None:
            w = self._adapters.weight(tenant)
            if w is not None:
                return w
        w = (self._cfg.tenant_weights or {}).get(tenant)
        return 1.0 if w is None else float(w)

    def _priority_of(self, tenant: str) -> int:
        if self._adapters is not None:
            p = self._adapters.priority(tenant)
            if p is not None:
                return p
        return int((self._cfg.tenant_priorities or {}).get(tenant, 0))

    def _slo_of(self, tenant: str) -> Optional[float]:
        if self._adapters is not None:
            s = self._adapters.slo_ttft_ms(tenant)
            if s is not None:
                return s
        return (self._cfg.tenant_slo_ttft_ms or {}).get(tenant)

    def slo_burn(self, tenant: str) -> float:
        """``tenant``'s SLO burn rate on this engine (0.0 when unknown)
        — the fleet router's deprioritize-burning-replicas signal."""
        return self._metrics.slo_burn(tenant)

    def _blocks_needed(self, prompt_len: int, max_new: int) -> int:
        """KV blocks a request reserves at admission: every position it
        can write (the last sampled token needs no write)."""
        total = min(prompt_len + max_new - 1, self._cfg.max_len)
        return blocks_for(total, self._cfg.block_size)

    def _overload_reason(self, prompt_len: int,
                         max_new: int) -> Tuple[str, str]:
        """Name the scarce resource behind a full admission queue:
        ``blocks_exhausted`` when slots are free but the paged pool
        cannot cover this request, else ``slots_full``. Racy reads —
        this labels an error message and a counter, it gates nothing."""
        s = self._cfg.max_slots
        if self._paged:
            free_slots = sum(r is None for r in self._slots)
            need = self._blocks_needed(prompt_len, max_new)
            free_blocks = self._blocks.free_count
            if free_slots > 0 and free_blocks < need:
                return ("blocks_exhausted",
                        f"{free_blocks}/{self._blocks.usable} KV blocks "
                        f"free, next request needs {need} — raise "
                        f"n_blocks or lower max_new_tokens")
            return ("slots_full",
                    f"all {s} decode slots busy and the queue is full — "
                    f"raise max_slots/max_queue or shed load")
        return ("slots_full",
                f"all {s} decode slots busy and the queue is full — "
                f"raise max_slots/max_queue or shed load")

    def generate(self, tokens: Sequence[int],
                 timeout: Optional[float] = None, **kw) -> Dict:
        """Synchronous :meth:`submit` (+ ``handle.result(timeout)``)."""
        return self.submit(tokens, **kw).result(timeout)

    def _active_rows(self) -> int:
        """Live decode slots plus block-starved held requests — with the
        queue depth (:meth:`~.engine.ReadinessMixin.load`), the
        fleet router's least-depth dispatch signal. Lock-free reads:
        approximate by design (it orders replicas, it gates nothing)."""
        return (sum(r is not None for r in self._slots)
                + len(self._held))

    def stats(self) -> Dict:
        """The ``/stats`` snapshot (augments :class:`ServeMetrics` with
        the slot/compile view; ``batch_fill_ratio`` here is decode-slot
        occupancy — live streams ÷ slots executed)."""
        snap = self._metrics.snapshot()
        snap["max_slots"] = self._cfg.max_slots
        snap["max_len"] = self._cfg.max_len
        snap["active_slots"] = sum(r is not None for r in self._slots)
        snap["peak_active_slots"] = self._peak_active
        snap["prefill_buckets"] = list(self._buckets)
        snap["kv_layout"] = self._cfg.kv_layout
        if self._paged:
            snap["block_size"] = self._cfg.block_size
            snap["blocks"] = self._blocks.gauges()
            hits = snap["generation"]["prefix_hits_total"]
            misses = snap["generation"]["prefix_misses_total"]
            snap["prefix_hit_rate"] = (hits / (hits + misses)
                                       if hits + misses else None)
            snap["chunked_prefill"] = self._cfg.chunked_prefill
            snap["prefix_digests"] = (
                list(self._blocks.route_digests())
                if self._cfg.prefix_reuse else [])
            # Per-tenant owned/budget block gauges — its OWN top-level
            # key (NOT inside "blocks": the fleet router sums those
            # gauges numerically across replicas).
            snap["blocks_by_tenant"] = self._blocks.tenant_gauges()
        snap["last_prefill_bucket"] = self._last_prefill_bucket
        if self._adapters is not None:
            snap["adapters_resident"] = len(self._adapters.resident())
            snap["adapter_table"] = self._adapters.gauges()
        snap["spec_k"] = self._spec.k if self._spec is not None else 0
        with self._stats_lock:
            snap["compiled"] = sorted(map(str, self._compiled_ids))
        snap["max_queue"] = self._cfg.max_queue
        return snap

    # -- multi-tenant adapter surface (fleet routing + lifecycle) ----------

    @property
    def adapters(self) -> Optional[AdapterRegistry]:
        """This engine's registry (None = base-only engine)."""
        return self._adapters

    def adapter_names(self) -> Optional[Tuple[str, ...]]:
        """Resident adapter names, or None when the engine carries no
        registry — the residency signal the fleet router's
        adapter-affine dispatch sorts on."""
        if self._adapters is None:
            return None
        return self._adapters.resident()

    def adapters_resident(self) -> Optional[int]:
        """Resident-adapter count for ``/healthz`` (None = no registry)."""
        names = self.adapter_names()
        return None if names is None else len(names)

    def prefix_digests(self) -> Tuple[str, ...]:
        """Advisory routing digests of the prefix chains this engine
        holds (either tier) — the residency signal the fleet router's
        prefix-affine dispatch sorts on. Empty for engines without a
        prefix registry."""
        if not (self._paged and self._cfg.prefix_reuse):
            return ()
        return self._blocks.route_digests()

    @property
    def route_block_size(self) -> int:
        """Block size a dispatcher must use to compute a request's
        routing digest so it matches this engine's advertised ones."""
        return self._cfg.block_size

    def load_adapter(self, name: str, adapter: Any,
                     quota: Optional[int] = None) -> int:
        """Hot-load ``adapter`` under ``name`` (the router's lazy-load
        path on an affinity miss). Raises ``ValueError`` without a
        registry or on a full table; never recompiles anything."""
        if self._adapters is None:
            raise ValueError(
                "engine has no AdapterRegistry — pass adapters= to "
                "GenerationEngine to serve adapters")
        return self._adapters.load(name, adapter, quota=quota)

    def prom_collect(self):
        """This engine's ``(meta, samples)`` in Prometheus terms —
        everything :meth:`stats` knows (TTFT, tokens/sec/user,
        block-pool gauges, prefix hit rate, rejection splits) plus the
        histograms, labeled ``engine="generate"`` (see
        :func:`~horovod_tpu.serve.metrics.collect_stats`)."""
        from .metrics import collect_stats
        return collect_stats(self.stats(), self._metrics.registry,
                             engine="generate")

    def prom_metrics(self) -> str:
        """Prometheus text exposition of :meth:`prom_collect` (the
        ``/metrics`` body when this engine serves alone)."""
        from ..obs.registry import render
        return render(*self.prom_collect())

    def shutdown(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the engine. ``drain=True`` finishes every stream already
        admitted (queued AND mid-generation) first; ``drain=False`` fails
        pending handles with :class:`ServerClosedError` and aborts
        in-flight streams. Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._adapters is not None:
            # Unhook the metric-fold listener: a registry SHARED across
            # replicas must not keep retired engines' metrics alive.
            self._adapters.remove_evict_listener(
                self._metrics.forget_tenant)
        if drain:
            self._queue.close()
        else:
            self._abort = True
            self._fail_pending()
        self._thread.join(timeout)
        # Unconditional second sweep: a DEAD loop (kill drill, loop
        # crash) joins instantly with its queue unserved, and a racing
        # submit can slip past the _closed check into an already-swept
        # queue — whatever is still pending here will never be served.
        self._fail_pending()

    def _fail_pending(self) -> None:
        cancelled = 0
        for req in self._queue.drain_pending():
            if not req.handle.done():
                req.handle._fail(ServerClosedError(
                    "server shut down before execution"))
                cancelled += 1
            self._req_done(req)
        if cancelled:
            self._metrics.on_shutdown_cancel(cancelled)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)

    # -- the continuous-batching loop --------------------------------------

    def _crash_dump(self, reason: str) -> None:
        """Flight-recorder post-mortem for THIS replica: one event
        naming every in-flight stream id, then the ring dump — what an
        operator reads after a replica death to know which streams the
        failover plane had to resume."""
        inflight = [r.stream_id for r in self._slots if r is not None]
        inflight += [r.stream_id for r in self._held]
        flightrec.record("serve_crash", replica=self.serve_name,
                         inflight=inflight, queued=len(self._queue))
        flightrec.dump(reason=f"serving replica {self.serve_name}: "
                              f"{reason}")

    def _loop(self):
        while True:
            try:
                self._beat = time.monotonic()
                act = faults.serve_hook(self.serve_name,
                                        self._streams_started)
                if act == "kill":
                    # Abrupt loop death: the thread exits WITHOUT
                    # failing its handles — a crashed process cannot
                    # deliver failures. The stranded streams are the
                    # fleet failover drill's whole point; the dump is
                    # the post-mortem a real dead replica would leave.
                    self._crash_dump("fault injection: replica_kill")
                    return
                if act == "proc_kill":
                    # Real process death: dump the post-mortem first
                    # (SIGKILL gives no atexit), then SIGKILL ourselves
                    # — the parent-side client sees a dead pid and
                    # broken streams, exactly what a crashed subprocess
                    # replica leaves behind.
                    self._crash_dump("fault injection: replica_proc_kill")
                    os.kill(os.getpid(), signal.SIGKILL)
                if act == "hang":
                    # Park forever with the thread ALIVE: only the
                    # stale-beat half of loop_alive() can catch this.
                    while True:
                        time.sleep(3600)
                if self._abort:
                    err = ServerClosedError(
                        "server shut down before completion")
                    for req in self._held:
                        req.handle._fail(err)
                        self._req_done(req)
                    self._held.clear()
                    self._fail_active(err)
                    return
                if self._prefetch_q:
                    self._apply_prefetches()
                free = [i for i, r in enumerate(self._slots) if r is None]
                n_active = self._cfg.max_slots - len(free)
                idle = n_active == 0 and not self._held
                # Pull EVERYTHING queued into the held line, not just
                # enough to fill the free slots: the scheduler is only
                # fair across tenants it can SEE — a quiet tenant parked
                # behind a chatty burst in the FIFO queue would
                # otherwise be invisible to it. Held requests keep
                # their max_queue admission ticket (``hold=True``), so
                # the door's backpressure bound is unchanged.
                want = len(self._queue) or (len(free) if idle else 0)
                if want > 0:
                    # Blocks ONLY when fully idle (no active streams,
                    # nothing held, an empty queue); with streams in
                    # flight it drains whatever is queued without waiting.
                    batch = self._queue.take_batch(want, 0.0, hold=True)
                    if not batch and idle:
                        return      # closed and drained, nothing in flight
                    for r in batch:
                        r.held_ticket = True
                    self._held.extend(batch)
                self._expire_held()
                # Admission order is the FairScheduler's pick — WDRR
                # over tenants, strict priorities above it, FIFO within
                # a tenant (one tenant degenerates to exact FIFO).
                blocked: set = set()
                budget_blocked: set = set()
                while self._held and free:
                    i = self._sched.pick(self._held,
                                         blocked=frozenset(blocked))
                    if i is None:
                        break   # every pending tenant is block-starved
                    req = self._held[i]
                    # The ticket covers the request only until its
                    # first admission ATTEMPT — from here it is "being
                    # served" (possibly block-starved), not "queued",
                    # and must not count against the door (an in-
                    # admission prefill can hold the loop for seconds).
                    if req.held_ticket:
                        req.held_ticket = False
                        self._queue.release_held()
                    outcome = self._admit(req, free[0])
                    if outcome in ("starved", "starved_budget"):
                        # This TENANT can't get KV blocks yet — decode
                        # steps below will free some. Only ITS line
                        # holds; other tenants keep admitting (the
                        # per-tenant half of blocks_exhausted).
                        blocked.add(req.tenant)
                        if outcome == "starved_budget":
                            budget_blocked.add(req.tenant)
                        continue
                    del self._held[i]
                    if outcome == "ok":
                        free.pop(0)
                preempted = False
                if (self._cfg.preempt and self._held
                        and (not free or blocked)
                        and any(r is not None for r in self._slots)):
                    preempted = self._maybe_preempt(budget_blocked)
                if any(r is not None for r in self._slots):
                    self._step_once()
                elif self._held and (self._prefetch_q or preempted):
                    # Held requests with nothing decoding but progress
                    # already in motion: a staged host-tier prefetch
                    # lands at the next iteration's top, or an eviction
                    # just freed the slot(s) the next admission pass
                    # fills. Not a stall.
                    pass
                elif self._held:
                    # Starved with nothing in flight: the submit-time
                    # pool-size and budget checks make this unreachable
                    # (every block is free or reclaimable — a tenant's
                    # own residue included — and need <= usable and
                    # <= budget). Fail loudly rather than spin.
                    req = self._held.popleft()
                    if req.held_ticket:
                        req.held_ticket = False
                        self._queue.release_held()
                    req.handle._fail(ServerOverloadedError(
                        "KV block pool cannot cover an admitted request "
                        "with the engine idle — admission accounting bug"))
                    self._req_done(req)
            except Exception as e:  # noqa: BLE001 — deliver, don't die
                # Every active stream is about to fail: leave the
                # post-mortem FIRST (the handles' owners may be remote
                # clients who only ever see a broken stream). Dumped
                # once per engine: the loop keeps serving after an
                # error, and a deterministic per-batch fault must not
                # pay an fsync'd dump on every occurrence inside the
                # hot loop (the ring keeps recording; a later DEATH —
                # kill, abort — still dumps the fresher events).
                if not self._loop_error_dumped:
                    self._loop_error_dumped = True
                    self._crash_dump(f"engine loop error: {e!r}")
                self._fail_active(e)

    def _fail_active(self, exc: BaseException) -> None:
        for i, req in enumerate(self._slots):
            if req is not None:
                req.handle._fail(exc)
                self._req_done(req)
                self._release_slot(i)

    def _release_slot(self, i: int) -> None:
        """Vacate slot ``i``: paged layouts return its blocks to the pool
        (refcount-aware — a shared prefix block frees only when its last
        reader ends) and trash-out its table row."""
        self._slots[i] = None
        self._positions[i] = -1
        self._adapter_idx[i] = -1
        if self._paged:
            self._blocks.release(self._slot_blocks[i])
            self._slot_blocks[i] = []
            self._tables[i] = TRASH_BLOCK

    # -- fair scheduling + preemption ---------------------------------------

    def _expire_held(self) -> None:
        """Fail deadline-expired requests parked in the held line NOW,
        not when they next reach a slot: an expired request must not
        keep its reserved admission position (the max_queue ticket)
        nor pin host-tier prefetches nobody else asked for."""
        now = time.monotonic()
        if not any(r.expired(now) for r in self._held):
            return
        expired = [r for r in self._held if r.expired(now)]
        self._held = deque(r for r in self._held if not r.expired(now))
        for req in expired:
            self._metrics.on_deadline_expired(
                (now - req.enqueued_at) * 1e3,
                tenant=self._tenant_label(req))
            req.handle._fail(DeadlineExceededError(
                f"deadline expired after "
                f"{(now - req.enqueued_at) * 1e3:.1f} ms in queue"))
            self._req_done(req)
            if req.held_ticket:
                req.held_ticket = False
                self._queue.release_held()
            self._release_prefetches(req)

    def _release_prefetches(self, req: _GenRequest) -> None:
        """Drop staged host-tier prefetches only ``req`` wanted (it
        left the held line unserved): each staged payload would burn a
        device block on landing, for a chain no surviving admission is
        waiting on. Keys another held request also staged stay."""
        if not req.prefetch_keys:
            return
        wanted: set = set()
        for other in self._held:
            wanted |= other.prefetch_keys
        drop = req.prefetch_keys - wanted
        req.prefetch_keys = set()
        if not drop:
            return
        self._prefetch_q = deque(
            e for e in self._prefetch_q if e[0] not in drop)
        self._prefetch_inflight -= drop

    def _maybe_preempt(self, budget_blocked: set) -> bool:
        """Preempt-by-evict: when a higher-priority pending request
        found no free slot (or no pool blocks), evict the LOWEST-
        priority active stream so the next iteration admits the high-
        priority one. Tenants starved on their OWN block budget don't
        count as waiting — evicting a neighbor frees pool blocks, never
        budget headroom. One victim per loop iteration: eviction paces
        with the decode steps, so a priority inversion cannot cascade
        into a mass eviction in one beat. Returns True when a stream
        was evicted — the loop counts that as progress (an eviction can
        empty every slot; the freed one is filled by the NEXT
        iteration's admission pass, not the idle-starvation guard)."""
        now = time.monotonic()
        waiting = [r for r in self._held
                   if r.tenant not in budget_blocked
                   and not r.expired(now)]
        if not waiting:
            return False
        top = max(self._priority_of(r.tenant) for r in waiting)
        # Victim: lowest priority class; ties evict the LATEST-admitted
        # stream (the least completed work lost to replay).
        prio, _, slot = min(
            (self._priority_of(r.tenant), -r.stream_id, i)
            for i, r in enumerate(self._slots) if r is not None)
        if top > prio:
            self._preempt(slot)
            return True
        return False

    def _preempt(self, slot: int) -> None:
        """Evict the stream in ``slot``, capturing its envelope exactly
        like a replica-death failover: everything already emitted is
        kept as an expect-prefix to regenerate suppressed-and-verified,
        the rng restarts from the seed, the ORIGINAL absolute deadline
        stays, and the request rejoins the held line (no new admission
        ticket — it was admitted once). Past ``preempt_retries``
        evictions the stream fails with terminal reason
        ``preempted_exhausted`` instead (under a fleet router that is
        additionally a failover cause — the envelope may still resume
        on another replica)."""
        req = self._slots[slot]
        req.retries += 1
        self._metrics.on_preempt("evicted",
                                 tenant=self._tenant_label(req))
        flightrec.record("serve_preempt", replica=self.serve_name,
                         stream=req.stream_id, tenant=req.tenant,
                         n_tokens=req.n_out, retries=req.retries)
        self._release_slot(slot)
        if req.retries > self._cfg.preempt_retries:
            self._metrics.on_preempt("exhausted",
                                     tenant=self._tenant_label(req))
            req.handle._fail(PreemptedError(
                f"stream {req.stream_id} (tenant {req.tenant!r}) "
                f"evicted {req.retries} times > preempt_retries="
                f"{self._cfg.preempt_retries}: preempted_exhausted — "
                f"re-submit, or raise the tenant's priority or the "
                f"retry budget"))
            self._req_done(req)
            return
        req.replay_expect = list(req.handle._tokens)
        req.replay_i = 0
        req.n_out = 0
        req.rng = np.random.default_rng(req.sampling.seed)
        req.t_admit = None
        self._held.append(req)

    def _req_emit(self, req: _GenRequest, tok: int) -> None:
        """Every sampled token flows through here. Normal streams emit
        straight to the handle (and count in the token counters); a
        stream resuming from preemption first regenerates its already-
        emitted prefix SUPPRESSED — each token verified against the
        captured envelope, none re-delivered, none re-counted — then
        emits new tokens. Divergence is impossible under the slot-row
        bit-identity contract, so it fails LOUDLY (an engine bug), like
        the admission accounting guard."""
        if req.replay_expect is not None:
            if req.replay_i < len(req.replay_expect):
                want = req.replay_expect[req.replay_i]
                if tok != want:
                    raise RuntimeError(
                        f"preemption replay diverged on stream "
                        f"{req.stream_id}: position {req.replay_i} "
                        f"regenerated {tok}, envelope expected {want} — "
                        f"the slot-row bit-identity contract is broken")
                req.replay_i += 1
                return
            req.replay_expect = None
            self._metrics.on_preempt("resumed",
                                     tenant=self._tenant_label(req))
        self._metrics.on_tokens(tenant=self._tenant_label(req))
        req.handle._emit(tok)

    def _paged_reserve(self, req: _GenRequest):
        """Reserve the blocks ``req`` needs: prefix-registry hits are
        retained (shared), the rest freshly allocated — or None when the
        pool can't cover it yet, or ``"wait"`` when the chain continues
        in the host tier under ``host_admission="wait"`` (the request
        holds the FIFO head while the kicked prefetch lands).
        Re-resolves hits after every reclaim sweep (an eviction can take
        chain entries the first lookup matched). Before hard-evicting
        registered prefixes, cold ones are OFFLOADED to the host tier
        (when configured) so a later admission can prefetch them back
        instead of recomputing.

        With a per-tenant block budget, returns ``"budget"`` when THIS
        tenant is over its cap and cannot get under it by offloading or
        reclaiming its OWN coldest blocks — a per-tenant starvation
        that must never hold another tenant's admission line."""
        n_total = self._blocks_needed(req.tokens.size, req.max_new)
        budget = self._blocks.budget(req.tenant)
        while True:
            hits = (self._blocks.lookup_prefix(req.tokens,
                                               salt=req.prefix_salt)
                    if self._cfg.prefix_reuse else [])
            hits = hits[:n_total]
            if self._host_cap:
                cont = self._blocks.host_lookup(
                    req.tokens, len(hits), salt=req.prefix_salt)
                if cont:
                    self._stage_prefetch(cont, req)
                    if self._cfg.host_admission == "wait":
                        return "wait"
                    # "miss": admit now on device-tier hits only — the
                    # suffix recomputes; the prefetch still lands for
                    # the NEXT admission. Never a stale read either way.
            if self._chunked:
                # A hit depth must be whole CHUNKS: the scan's cold and
                # hit programs share trip boundaries only at multiples
                # of the chunk, and at least one prompt token must
                # remain in the suffix to score the sampled row.
                cb = self._cfg.chunk_blocks
                cap = ((int(req.tokens.size) - 1)
                       // self._cfg.chunk_tokens) * cb
                n_hit = min(len(hits), cap)
                hits = hits[:n_hit - n_hit % cb]
            need = n_total - len(hits)
            if budget is not None:
                over = (self._blocks.owned_count(req.tenant) + need
                        - budget)
                if over > 0:
                    # Over ITS budget: this tenant frees its OWN coldest
                    # blocks first — host-tier offload, then registry
                    # reclaim — and starves ALONE if neither helps.
                    if self._host_cap and self._offload_for(
                            over, owner=req.tenant):
                        continue
                    if not self._blocks.reclaim(
                            self._blocks.free_count + over,
                            owner=req.tenant):
                        return "budget"
                    continue
            free = self._blocks.free_count
            if free >= need:
                self._blocks.retain(hits)
                fresh = self._blocks.alloc(need, owner=req.tenant)
                return hits, fresh, n_total
            if self._host_cap and self._offload_for(need - free):
                continue
            if not self._blocks.reclaim(need):
                return None

    # -- host tier (offload / prefetch) ------------------------------------

    def _offload_for(self, shortfall: int,
                     owner: Optional[str] = None) -> bool:
        """Move up to ``shortfall`` cold registered-prefix blocks to the
        host tier (device bytes snapshotted to host numpy staging, then
        committed — the manager re-validates under its lock, so a hit
        landing mid-copy cancels that block's offload). Returns whether
        any device block was freed. ``owner`` restricts the victims to
        that tenant's blocks (the over-budget self-offload path)."""
        # Per-block gathers with a SCALAR index: one compiled program
        # reused for every offload. A batched fancy-index gather would
        # recompile for each distinct victim-set size.
        moved = 0
        for key, blk in self._blocks.offload_candidates(shortfall,
                                                        owner=owner):
            payload = {"k": np.asarray(self._cache["k"][:, blk]),
                       "v": np.asarray(self._cache["v"][:, blk])}
            if self._blocks.offload_commit(key, payload):
                moved += 1
        if moved:
            self._metrics.on_kv_offload(moved)
        return moved > 0

    def _stage_prefetch(self, cont, req: _GenRequest) -> None:
        """Queue host→device copies for a chain continuation found in
        the host tier; applied at the next loop top, never inside a
        decode step. Idempotent per key while a copy is in flight.
        ``req`` records the keys it staged (released if it expires
        while parked) and owns the blocks the copies will land in."""
        now = time.monotonic()
        for key, payload in cont:
            req.prefetch_keys.add(key)
            if key in self._prefetch_inflight:
                continue
            self._prefetch_inflight.add(key)
            self._prefetch_q.append((key, payload, now, req.tenant))

    def _apply_prefetches(self) -> None:
        """Land staged prefetches: allocate a device block, write the
        staged bytes, promote the registry entry (idempotent against an
        admission that re-registered the chain cold meanwhile — see
        :meth:`BlockManager.promote`). Entries that cannot get a device
        block yet stay queued for the next iteration; the loop never
        blocks here. Writes use a SCALAR block index so the scatter
        compiles once and is reused for every prefetch."""
        for _ in range(len(self._prefetch_q)):
            key, payload, t0, owner = self._prefetch_q.popleft()
            if (self._blocks.free_count < 1
                    and not self._offload_for(1)
                    and not self._blocks.reclaim(1)):
                # Evict by OFFLOAD first: landing one chain by
                # destroying another turns the host tier's preservation
                # into mutual eviction under rotation.
                self._prefetch_q.append((key, payload, t0, owner))
                continue
            try:
                blk = self._blocks.alloc(1, owner=owner)[0]
            except RuntimeError:
                self._prefetch_q.append((key, payload, t0, owner))
                continue
            k = self._cache["k"].at[:, blk].set(
                jnp.asarray(payload["k"], self._cache["k"].dtype))
            v = self._cache["v"].at[:, blk].set(
                jnp.asarray(payload["v"], self._cache["v"].dtype))
            self._cache = {"k": k, "v": v,
                           "lengths": self._cache["lengths"]}
            self._blocks.promote(key, blk)
            self._prefetch_inflight.discard(key)
            self._metrics.on_kv_prefetch(time.monotonic() - t0)

    def _admit(self, req: _GenRequest, slot: int) -> str:
        """Prefill ``req`` into ``slot`` and emit its first token.
        Returns ``"ok"`` (slot occupied), ``"done"`` (expired, failed, or
        finished on its first token — slot stays free), ``"starved"``
        (paged only: not enough free KV blocks yet — the request stays
        held and the slot stays free), or ``"starved_budget"`` (the
        request's TENANT is over its own block budget — only its line
        blocks; the scheduler keeps admitting everyone else)."""
        now = time.monotonic()
        if req.expired(now):
            self._metrics.on_deadline_expired(
                (now - req.enqueued_at) * 1e3,
                tenant=self._tenant_label(req))
            req.handle._fail(DeadlineExceededError(
                f"deadline expired after "
                f"{(now - req.enqueued_at) * 1e3:.1f} ms in queue"))
            self._req_done(req)
            return "done"
        reservation = None
        row: List[int] = []
        read_row = None
        if self._paged:
            reservation = self._paged_reserve(req)
            if reservation == "budget":
                return "starved_budget"
            if not isinstance(reservation, tuple):
                # None = block-starved, "wait" = host-tier chain still
                # prefetching; either way the request stays held (only
                # its own tenant's line waits) and the slot stays free.
                return "starved"
        req.t_admit = now
        self._streams_started += 1     # the serve_hook @stream counter
        try:
            length = int(req.tokens.size)
            args = [self._params]
            if self._adapters is not None:
                # The table read HERE is the hot-load boundary: a load
                # committed before this admission is visible, one racing
                # it lands at the next boundary — never mid-program.
                args.append(self._adapters.table())
            if self._chunked:
                hits, fresh, n_total = reservation
                row = hits + fresh
                bs = self._cfg.block_size
                ct = self._cfg.chunk_tokens
                # The compiled program starts at the first non-shared
                # block: the bucket is drawn on the SUFFIX length, so a
                # deep hit executes a genuinely smaller program.
                start = len(hits) * bs
                suf_len = length - start
                bucket = bucket_for(suf_len, self._chunked_buckets)
                toks = np.zeros((bucket,), np.int32)
                toks[:suf_len] = req.tokens[start:]
                exe = self._compile(("chunked_prefill", bucket))
                args += [toks, self._cache, np.asarray(slot, np.int32),
                         np.asarray(length, np.int32),
                         np.asarray(start, np.int32)]
                if self._adapters is not None:
                    args.append(np.asarray(req.adapter_slot, np.int32))
                nb = self._cfg.blocks_per_slot
                read_row = np.full((nb,), TRASH_BLOCK, np.int32)
                read_row[:n_total] = row
                # Per-chunk write targets: only the fresh blocks the
                # suffix's PROMPT positions land in — hit blocks are
                # never written at all, generation blocks and bucket
                # padding write to the trash block.
                suffix_blocks = row[len(hits):blocks_for(length, bs)]
                wflat = np.full((bucket // bs,), TRASH_BLOCK, np.int32)
                wflat[:len(suffix_blocks)] = suffix_blocks
                args += [wflat.reshape(bucket // ct,
                                       self._cfg.chunk_blocks),
                         read_row]
                n_full = length // bs
                if n_full > 0:
                    self._metrics.on_prefix(len(hits), n_full)
                self._metrics.on_chunked_prefill(bucket // ct,
                                                 start // ct)
            else:
                bucket = bucket_for(length, self._buckets)
                toks = np.zeros((bucket,), np.int32)
                toks[:length] = req.tokens
                exe = self._compile(("prefill", bucket))
                args += [toks, self._cache, np.asarray(slot, np.int32),
                         np.asarray(length, np.int32)]
                if self._adapters is not None:
                    args.append(np.asarray(req.adapter_slot, np.int32))
                if self._paged:
                    hits, fresh, n_total = reservation
                    row = hits + fresh
                    nb = self._cfg.blocks_per_slot
                    read_row = np.full((nb,), TRASH_BLOCK, np.int32)
                    read_row[:n_total] = row
                    # Writes aimed at SHARED prefix blocks go to the
                    # trash block: the recomputed prefix K/V is already
                    # resident, and a sharer must never touch bytes
                    # other streams read.
                    write_row = read_row.copy()
                    write_row[:len(hits)] = TRASH_BLOCK
                    n_full = length // self._cfg.block_size
                    if self._cfg.prefix_reuse and n_full > 0:
                        self._metrics.on_prefix(len(hits), n_full)
                    args.append(write_row)
            self._last_prefill_bucket = bucket
            cache, last_logits = exe(*args)
            logits = np.asarray(last_logits)    # blocks
        except Exception as e:  # noqa: BLE001
            if reservation is not None:
                hits, fresh, _ = reservation
                self._blocks.release(hits + fresh)
            req.handle._fail(e)
            self._req_done(req)
            return "done"
        self._cache = cache
        if self._paged and self._cfg.prefix_reuse:
            # Pin the prompt's full blocks for future admissions — the
            # prefix now lives in the pool whether or not this stream
            # survives its first token.
            n_full = int(req.tokens.size) // self._cfg.block_size
            if n_full > 0:
                self._blocks.register_prefix(
                    req.tokens, row, n_full, salt=req.prefix_salt,
                    route_digest=prefix_route_digest(
                        req.tokens, self._cfg.block_size, req.adapter))
        if req.replay_expect is None:
            # A resuming stream's first token was already DELIVERED
            # (and its TTFT recorded) before the eviction — re-stamping
            # here would double-count the tenant's SLO outcomes.
            req.t_first = time.monotonic()
            self._metrics.on_first_token(
                (req.t_first - req.enqueued_at) * 1e3,
                tenant=self._tenant_label(req),
                slo_ms=self._slo_of(req.tenant))
        tok = req.sample(logits)
        req.n_out = 1
        self._req_emit(req, tok)
        reason = self._finish_reason(req, tok, next_pos=int(req.tokens.size))
        if reason:
            self._finish(req, reason)
            if self._paged:
                self._blocks.release(row)
            return "done"
        self._slots[slot] = req
        self._positions[slot] = int(req.tokens.size)
        self._last[slot] = tok
        self._adapter_idx[slot] = req.adapter_slot
        if self._paged:
            self._slot_blocks[slot] = row
            self._tables[slot] = read_row
        return "ok"

    def _step_once(self) -> None:
        """One decode-step boundary: the speculative draft→verify→accept
        step when speculation is configured, the plain one-token decode
        otherwise."""
        if self._spec is None:
            self._decode_once()
        else:
            self._spec_once()

    def _spec_once(self) -> None:
        """Draft k tokens per slot host-side, verify all k+1 positions in
        ONE compiled forward, accept per slot.

        Acceptance is per-slot VARIABLE: a slot whose drafts all miss
        still emits one token (verify row 0 is bitwise the decode-step
        logits), and a step where NO slot drafted anything falls through
        to the plain decode program — speculation is an optimization,
        never a liveness dependency. Greedy acceptance emits exactly the
        one-token stream (digest-pinned in ci.sh); sampled acceptance is
        the seeded rejection rule in :mod:`.spec`. Every accepted token
        flows through ``handle._emit`` one at a time, so fleet failover
        envelopes replay a speculated stream token-for-token unchanged.
        """
        k = self._spec.k
        w = k + 1
        t0 = time.monotonic()
        active = [i for i, r in enumerate(self._slots) if r is not None]
        # Pad columns repeat the slot's last token: always a valid id,
        # and the rows are never read by the host (their K/V writes are
        # overwritten before the mask ever exposes them).
        toks = np.repeat(self._last.copy()[:, None], w, axis=1)
        drafts: Dict[int, np.ndarray] = {}
        for i in active:
            req = self._slots[i]
            # Most tokens this stream may still emit (budget + cache
            # room); drafting past cap-1 can't be accepted AND keeps
            # every write inside the blocks admission reserved.
            cap = min(req.max_new - req.n_out,
                      self._cfg.max_len - int(self._positions[i]))
            if cap < 2:
                continue
            # [:n_out]: for a normal stream that IS the whole emitted
            # list, but a preemption replay must draft from only the
            # regenerated-so-far prefix — the envelope's future tokens
            # would otherwise change the drafts, change the rng draws
            # sampled acceptance consumes, and break bit-identity.
            ctx = np.concatenate(
                [np.asarray(req.tokens, np.int64),
                 np.asarray(req.handle._tokens[:req.n_out], np.int64)])
            d = np.asarray(self._drafter.propose(ctx, min(k, cap - 1)),
                           np.int64).ravel()[:min(k, cap - 1)]
            d = d[(d >= 0) & (d < self._model_cfg.vocab)]
            if d.size:
                drafts[i] = d
                toks[i, 1:1 + d.size] = d
        draft_ms = (time.monotonic() - t0) * 1e3
        if not drafts:
            # Plain one-token step (still counted: tokens-per-step is an
            # EFFECTIVE rate over every step speculation supervised).
            self._decode_once()
            self._metrics.on_spec_step(0, 0, len(active), draft_ms, 0.0)
            return
        t1 = time.monotonic()
        args = [self._params]
        if self._adapters is not None:
            args.append(self._adapters.table())
        args += [toks, self._cache, self._positions.copy()]
        if self._adapters is not None:
            args.append(self._adapter_idx.copy())
        if self._paged:
            args.append(self._tables.copy())
        cache, logits = self._compile(("verify", w))(*args)
        logits_np = np.asarray(logits)          # [S, W, vocab], blocks
        self._cache = cache
        exec_ms = (time.monotonic() - t1) * 1e3
        self._peak_active = max(self._peak_active, len(active))
        self._metrics.on_batch(self._cfg.max_slots, len(active), exec_ms,
                               len(self._queue) + len(self._held))
        proposed = accepted = emitted_total = 0
        for i in active:
            req = self._slots[i]
            rows = logits_np[i]
            d = drafts.get(i)
            if d is None:
                cand, hits = [req.sample(rows[0])], 0
            elif req.sampling.temperature <= 0:
                cand, hits = accept_greedy(rows, d)
            else:
                cand, hits = accept_sampled(rows, d, req.probs, req.rng)
            emitted = 0
            reason = None
            for tok in cand:
                tok = int(tok)
                req.n_out += 1
                self._req_emit(req, tok)
                self._positions[i] += 1
                self._last[i] = tok
                emitted += 1
                reason = self._finish_reason(
                    req, tok, next_pos=int(self._positions[i]))
                if reason:
                    break
            n_prop = int(d.size) if d is not None else 0
            # EOS/length can truncate mid-acceptance; only tokens that
            # actually reached the stream count as accepted drafts.
            n_hit = min(hits, emitted)
            req.spec_proposed += n_prop
            req.spec_accepted += n_hit
            proposed += n_prop
            accepted += n_hit
            emitted_total += emitted
            if reason:
                # Counters first: _finish stamps the per-request spec
                # accounting into the result info.
                self._finish(req, reason)
                self._release_slot(i)
        self._metrics.on_spec_step(proposed, accepted, emitted_total,
                                   draft_ms, exec_ms)

    def _decode_once(self) -> None:
        t0 = time.monotonic()
        args = [self._params]
        if self._adapters is not None:
            args.append(self._adapters.table())   # hot-load boundary
        args += [self._last.copy(), self._cache, self._positions.copy()]
        if self._adapters is not None:
            args.append(self._adapter_idx.copy())
        if self._paged:
            args.append(self._tables.copy())
        cache, logits = self._compile("decode")(*args)
        logits_np = np.asarray(logits)          # blocks
        self._cache = cache
        exec_ms = (time.monotonic() - t0) * 1e3
        active = [i for i, r in enumerate(self._slots) if r is not None]
        self._peak_active = max(self._peak_active, len(active))
        self._metrics.on_batch(self._cfg.max_slots, len(active), exec_ms,
                               len(self._queue) + len(self._held))
        for i in active:
            req = self._slots[i]
            tok = req.sample(logits_np[i])
            req.n_out += 1
            self._req_emit(req, tok)
            self._positions[i] += 1
            self._last[i] = tok
            reason = self._finish_reason(req, tok,
                                         next_pos=int(self._positions[i]))
            if reason:
                self._finish(req, reason)
                self._release_slot(i)

    def _finish_reason(self, req: _GenRequest, tok: int,
                       next_pos: int) -> Optional[str]:
        if req.eos is not None and tok == req.eos:
            return "eos"
        if req.n_out >= req.max_new or next_pos >= self._cfg.max_len:
            return "length"
        return None

    def _finish(self, req: _GenRequest, reason: str) -> None:
        if (req.replay_expect is not None
                and req.replay_i < len(req.replay_expect)):
            # Finishing mid-replay means the regenerated stream ended
            # EARLIER than its own recorded envelope — divergence, the
            # same impossible-by-contract condition _req_emit guards.
            raise RuntimeError(
                f"preemption replay of stream {req.stream_id} finished "
                f"({reason}) at position {req.replay_i} but its envelope "
                f"holds {len(req.replay_expect)} tokens — the slot-row "
                f"bit-identity contract is broken")
        now = time.monotonic()
        gen_s = now - req.t_first
        ttft_ms = (req.t_first - req.enqueued_at) * 1e3
        self._metrics.on_generation_end(req.n_out, gen_s,
                                        tenant=self._tenant_label(req))
        # queue_ms is the ADMISSION wait (enqueue → slot), not TTFT —
        # latency.queue_* must isolate queue pressure from prefill cost.
        self._metrics.on_response((now - req.enqueued_at) * 1e3,
                                  (req.t_admit - req.enqueued_at) * 1e3)
        self._req_done(req)
        flightrec.record("serve_complete", replica=self.serve_name,
                         stream=req.stream_id, n_tokens=req.n_out,
                         reason=reason)
        req.handle._finish({
            "tokens": list(req.handle._tokens),
            "finish_reason": reason,
            "n_tokens": req.n_out,
            "ttft_ms": ttft_ms,
            "tenant": req.tenant,
            "adapter": req.adapter,
            "tokens_per_sec": ((req.n_out - 1) / gen_s
                               if req.n_out > 1 and gen_s > 0 else None),
            # Per-request speculation accounting (None = spec off).
            "spec_accept_rate": (
                (req.spec_accepted / req.spec_proposed
                 if req.spec_proposed else 0.0)
                if self._spec is not None else None),
        })
