#!/usr/bin/env python
"""Load generator for :mod:`horovod_tpu.serve` — the latency/throughput
curve behind the serving numbers in ``docs/inference.md``.

Open-loop (arrival times are scheduled at the target rate regardless of
completion — closed-loop generators hide overload by self-throttling,
the classic coordinated-omission trap), mixed request sizes, per-request
deadline. For each target QPS it reports achieved throughput, e2e
latency p50/p99, batch-fill ratio, and the two drop classes the
backpressure contract distinguishes (overload rejects vs deadline
expiries).

    JAX_PLATFORMS=cpu python bin/serve_bench.py --qps 200 --duration 5
    python bin/serve_bench.py --qps 50,100,200,400 --duration 10  # curve

``--mode generate`` drives the continuous-batching generation engine
instead (a small transformer LM, mixed prompt lengths): per operating
point it reports p50/p99 **time-to-first-token**, per-user and aggregate
tokens/sec, and decode-slot occupancy — and prints one JSON line per
point (``peak_bytes_per_chip`` from the device's ``memory_stats``,
KV-cache bytes, peak concurrent streams, block-pool and prefix-cache
gauges) so the fixed-HBM capacity claims are checkable
from the bench row. ``--json FILE`` additionally appends the lines to a
file (the ci.sh capacity/prefix legs parse it).

    JAX_PLATFORMS=cpu python bin/serve_bench.py --mode generate \
        --qps 20 --duration 5

``--kv-layout paged`` (with ``--block-size``/``--n-blocks``/
``--prefix-reuse``/``--prefix-tokens``) serves the paged KV cache;
``--cache-mb`` fixes the KV-cache byte budget and derives the layout's
capacity from it (contiguous: slots = budget ÷ full-depth reservation;
paged: pool = budget ÷ block bytes, slots = what the pool can hold of
typical requests) — the concurrent-streams-capacity comparison at equal
cache bytes.

``--adapters N`` serves multi-tenant traffic: N seeded LoRA fine-tunes
(tenants ``a0..aN-1``) loaded next to the ``base`` model, arrivals drawn
per ``--adapter-mix`` weights from per-tenant deterministic prompt
streams. EVERY generate-mode JSON line then stamps the adapter fields
(``adapters``, ``adapter_mix``, ``tenant_sent``/``tenant_completed``)
and a per-tenant ``stream_digests`` map extending the PR-11 digest —
``--adapter-only TENANT`` replays the SAME arrival schedule submitting
only that tenant's requests, so ci.sh can pin each tenant's mixed-batch
digest against its single-tenant reference run.

``--replicas N`` serves the generate load through a ``FleetRouter`` of
N engine replicas (least-depth dispatch, one front door); adding
``--autoscale`` starts at ``--min-replicas`` and lets the queue-depth
``FleetAutoscaler`` grow toward N under load and drain-shrink back when
traffic stops. Fleet runs append the per-point rows PLUS one final
``{"fleet": true, ...}`` summary line (scale events, final membership,
dispatch split, lost streams) — the ci.sh closed-loop autoscaler drill
asserts grow >= 1, shrink back to the floor, zero lost streams, and a
``stream_digest`` identical to the single-replica run of the same
seeded traffic.

``--chaos CLAUSE`` arms the serving-plane fault injector
(``testing/faults.py``) for the run: ``replica_kill=r1@stream=3`` kills
replica r1's engine loop at its 3rd admitted stream,
``replica_hang=...`` wedges it instead, ``slow_step=MS`` slows every
decode iteration. With ``--replicas N`` the FleetRouter's deterministic
failover must then resume every stranded stream bit-identically — the
ci.sh serving chaos drill compares the per-tenant ``stream_digests``
against an unkilled single-replica reference and asserts
``failover.resumed >= 1`` with zero lost streams. ``--temperature`` /
``--top-k`` switch the traffic to seeded sampling (per-request seeds
are a pure function of the tenant + arrival index, so digests stay
run-to-run comparable) — failover bit-identity is pinned for greedy
AND sampled streams.

Exit status is nonzero if any *in-deadline* request was dropped at the
configured operating point — the regression gate ci.sh's serve smokes
rely on (the generate smoke additionally requires nonzero tokens/sec).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from horovod_tpu.utils.chips import enable_compile_cache  # noqa: E402

# In the environment too, so subprocess replicas share the directory.
enable_compile_cache()


def _percentile(xs, q):
    return float(np.percentile(xs, q * 100)) if xs else float("nan")


def _peak_bytes_per_chip():
    """Per-chip peak HBM bytes from the runtime's allocator stats, or
    None where the backend keeps none (CPU), so the fixed-HBM capacity
    claim is checkable from the JSON row."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — stats are best-effort telemetry
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


# The generate-mode bench model (vocab/d_model/heads/layers below):
# bytes per cached token position = 2 (K and V) · n_layers · d_model · 4
# (f32) — the unit both layouts' capacity math is written in.
_GEN_MODEL = dict(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128)
_GEN_BYTES_PER_TOKEN = 2 * _GEN_MODEL["n_layers"] * _GEN_MODEL["d_model"] * 4


def _gen_model(args):
    """Bench model dims. ``--model-dim`` widens the model (d_ff = 2·d)
    so a drill can sit in the regime the KV hierarchy is built for:
    prefill compute per chunk much larger than a block copy, as on a
    real accelerator. Default keeps the historical tiny model."""
    d = int(getattr(args, "model_dim", 0) or 0)
    if not d:
        return dict(_GEN_MODEL)
    return dict(vocab=256, d_model=d, n_heads=4, n_layers=2, d_ff=2 * d)


def _gen_bpt(args):
    m = _gen_model(args)
    return 2 * m["n_layers"] * m["d_model"] * 4


def _gen_capacity(args):
    """Resolve (max_slots, n_blocks, cache_bytes) for the generate
    engine. With ``--cache-mb`` the budget is FIXED and capacity derives
    from the layout — the whole point of the paged comparison:

    * contiguous: each slot reserves ``max_len`` positions, so
      slots = budget // (max_len · bytes/token);
    * paged: the pool is budget // (block_size · bytes/token) blocks —
      the reserved trash block is charged AGAINST the budget (usable
      capacity is one block less), not added on top — and slots = how
      many TYPICAL requests (longest bench prompt + generated tokens)
      the usable pool holds, capped at 64 so the decode program stays
      small on a CPU host.
    """
    if not args.cache_mb:
        n_blocks = args.n_blocks if args.n_blocks else None
        return args.slots, n_blocks, None
    bpt = _gen_bpt(args)
    budget = int(args.cache_mb * 2 ** 20)
    if args.kv_layout == "contiguous":
        slots = max(1, budget // (args.max_len * bpt))
        return slots, None, slots * args.max_len * bpt
    block_bytes = args.block_size * bpt
    n_blocks = max(2, budget // block_bytes)
    # Typical request: the longest bench prompt (prefix + 16) plus the
    # generated tokens (the last sampled token needs no cache write).
    typical = args.prefix_tokens + 16 + args.gen_tokens - 1
    per_req = -(-typical // args.block_size)
    slots = max(1, min(64, (n_blocks - 1) // per_req))
    return slots, n_blocks, n_blocks * block_bytes


def _build_engine(args):
    import jax
    import flax.linen as nn

    from horovod_tpu import serve

    class _BenchMLP(nn.Module):
        """Small but not trivial: two matmuls deep enough that XLA_EXECUTE
        is visible on the timeline, small enough that a laptop CPU clears
        hundreds of QPS — the bench measures the serving plane, not the
        model."""

        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Dense(256)(x)
            x = nn.relu(x)
            x = nn.Dense(256)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = _BenchMLP()
    item_shape = (args.features,)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1,) + item_shape, np.float32))
    cfg = serve.ServeConfig(max_batch=args.max_batch,
                            batch_timeout_ms=args.batch_timeout_ms,
                            max_queue=args.max_queue,
                            default_deadline_ms=args.deadline_ms)
    eng = serve.Engine(lambda v, x: model.apply(v, x, train=False),
                       variables, item_shape=item_shape, config=cfg)
    t0 = time.monotonic()
    eng.warmup()
    print(f"warmup: {len(serve.bucket_sizes(args.max_batch))} buckets "
          f"pre-compiled in {time.monotonic() - t0:.2f} s")
    return eng


def _bench_tenants(args):
    """Tenant names + normalized arrival weights for this run:
    ``base`` plus ``a0..aN-1`` (uniform unless ``--adapter-mix``)."""
    tenants = ["base"] + [f"a{i}" for i in range(args.adapters)]
    if args.adapter_mix:
        weights = [float(w) for w in args.adapter_mix.split(",")]
        if len(weights) != len(tenants) or any(w < 0 for w in weights) \
                or not sum(weights) > 0:
            raise SystemExit(
                f"--adapter-mix needs {len(tenants)} non-negative "
                f"comma-separated weights (base first, then "
                f"{tenants[1:]}), got {args.adapter_mix!r}")
    else:
        weights = [1.0] * len(tenants)
    total = sum(weights)
    return tenants, [w / total for w in weights]


def _parse_tenant_map(spec: str, what: str, cast):
    """``"base:1,a0:4"`` → ``{"base": cast("1"), "a0": cast("4")}`` —
    the shared parser behind --tenant-weights / --priority-mix /
    --tenant-slo-ms. Raises SystemExit with a usable message (argparse
    p.error re-raises it) on malformed pairs."""
    out = {}
    if not spec:
        return out
    for pair in spec.split(","):
        name, sep, val = pair.partition(":")
        name = name.strip()
        if not sep or not name:
            raise SystemExit(
                f"{what} must be comma-separated tenant:value pairs "
                f"(e.g. 'base:1,a0:4'), got {pair!r}")
        try:
            out[name] = cast(val)
        except ValueError:
            raise SystemExit(f"{what}: bad value {val!r} for {name!r}")
    return out


def _bench_adapters(args, cfg):
    """The run's LoRA plane: (lora_cfg, {name: host adapter tree}) —
    seeded, B randomized so the M tenants are genuinely DISTINCT
    fine-tunes (distinct streams, checkable digests)."""
    if not args.adapters:
        return None, None
    import jax

    from horovod_tpu.parallel.lora import LoraConfig, init_adapter
    lora = LoraConfig(rank=args.adapter_rank)
    trees = {f"a{i}": None if args.replica_procs else init_adapter(
                 jax.random.PRNGKey(100 + i), cfg, lora, b_scale=0.5)
             for i in range(args.adapters)}
    return lora, trees


def _build_gen_engine(args):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel.transformer import (TransformerConfig,
                                                  init_params)
    from horovod_tpu import serve

    # Small but real: the bench measures the serving plane (slot churn,
    # prefill/decode interleave, streaming), not model quality.
    cfg = TransformerConfig(**_gen_model(args), dtype=jnp.float32,
                            unembed_dtype=jnp.float32, attn_backend="xla")
    # Subprocess replicas each need a chip of their own, so their parent
    # must stay off the jax backend: the children re-derive params (and
    # adapters) from the spec's seeds and the parent builds none.
    params = None if args.replica_procs else init_params(
        jax.random.PRNGKey(0), cfg)
    slots, n_blocks, cache_bytes = _gen_capacity(args)
    gcfg = serve.GenerationConfig(
        max_slots=slots, max_len=args.max_len,
        max_queue=args.max_queue, default_deadline_ms=args.deadline_ms,
        default_max_new_tokens=args.gen_tokens,
        kv_layout=args.kv_layout,
        # SLO-aware multi-tenancy knobs (empty maps = neutral policy;
        # GenerationConfig treats None and absent alike). These are
        # plain JSON-able dicts, so subprocess replica specs carry them
        # through dataclasses.asdict(gcfg) unchanged.
        **({"tenant_weights": args.tenant_weights_map}
           if args.tenant_weights_map else {}),
        **({"tenant_priorities": args.priority_mix_map}
           if args.priority_mix_map else {}),
        **({"tenant_slo_ttft_ms": args.tenant_slo_ms_map}
           if args.tenant_slo_ms_map else {}),
        preempt_retries=args.preempt_retries,
        **({"block_size": args.block_size, "n_blocks": n_blocks,
            "prefix_reuse": args.prefix_reuse,
            "paged_kernel": args.paged_kernel,
            "chunked_prefill": args.chunked_prefill,
            "chunk_blocks": args.chunk_blocks,
            "host_blocks": args.host_blocks,
            "host_admission": args.host_admission}
           if args.kv_layout == "paged" else {}))
    if cache_bytes is None:
        if args.kv_layout == "paged":
            cache_bytes = (gcfg.resolved_n_blocks * gcfg.block_size
                           * _gen_bpt(args))
        else:
            cache_bytes = slots * args.max_len * _gen_bpt(args)
    lora, adapter_trees = _bench_adapters(args, cfg)
    spec_cfg = serve.SpecConfig(k=args.spec_k) if args.spec_k else None

    def _registry():
        if not adapter_trees:
            return None
        reg = serve.AdapterRegistry(cfg, lora,
                                    capacity=len(adapter_trees))
        for name, tree in sorted(adapter_trees.items()):
            reg.load(name, tree)
        return reg

    if args.replicas > 1 or args.autoscale or args.replica_procs:
        # Fleet mode: N replicas (each its own slots/block pool — and
        # its own adapter table — over the SHARED read-only params)
        # behind one FleetRouter. --autoscale starts at --min-replicas
        # and lets the queue-depth control loop grow toward --replicas;
        # static fleets warm all N up front. --replica-procs swaps the
        # thread-engine factory for subprocess workers — each child
        # re-derives the SAME params from the spec's seed, so stream
        # digests stay comparable across topologies.
        if args.replica_procs:
            import dataclasses
            spec = {
                "model": dict(_gen_model(args), dtype="float32",
                              unembed_dtype="float32",
                              attn_backend="xla"),
                "seed": 0,
                "generation": dataclasses.asdict(gcfg),
            }
            if spec_cfg is not None:
                spec["spec"] = spec_cfg.to_spec()
            if adapter_trees:
                # Seeds, not bytes: each child re-derives the SAME
                # trees _bench_adapters built here (PRNGKey(100+i),
                # b_scale=0.5), so per-tenant digests stay comparable
                # across thread and subprocess topologies.
                spec["adapters"] = {
                    "rank": args.adapter_rank, "alpha": lora.alpha,
                    "capacity": len(adapter_trees),
                    "entries": [{"name": f"a{i}", "seed": 100 + i,
                                 "b_scale": 0.5}
                                for i in range(args.adapters)],
                }
            factory = serve.spawn_replica_factory(spec)
        else:
            factory = lambda name: serve.GenerationEngine(  # noqa: E731
                params, cfg, gcfg, adapters=_registry(), spec=spec_cfg)
        initial = args.min_replicas if args.autoscale else args.replicas
        eng = serve.FleetRouter(
            factory=factory, initial=initial,
            # Subprocess children boot with EVERY tenant resident (the
            # spec carries them), so the lazy-load path has nothing to
            # do — and couldn't ship a host tree over HTTP anyway.
            adapter_source=(adapter_trees.__getitem__
                            if adapter_trees and not args.replica_procs
                            else None))
        eng.bench_cache_bytes = cache_bytes    # per REPLICA (pool grows
        t0 = time.monotonic()                  # with the fleet)
        warmed = eng.warmup()
        print(f"warmup [{args.kv_layout}, fleet {len(warmed)} replica(s) "
              f"x slots={slots}]: pre-compiled in "
              f"{time.monotonic() - t0:.2f} s")
        if args.autoscale:
            eng.bench_autoscaler = serve.FleetAutoscaler(
                eng, min_replicas=args.min_replicas,
                max_replicas=args.replicas,
                high_watermark=args.scale_high,
                low_watermark=args.scale_low,
                breach_up=2, breach_down=2,
                cooldown_s=1.0, interval_s=0.25).start()
        return eng
    eng = serve.GenerationEngine(params, cfg, gcfg, adapters=_registry(),
                                 spec=spec_cfg)
    eng.bench_cache_bytes = cache_bytes      # stamped into the JSON rows
    t0 = time.monotonic()
    warmed = eng.warmup()
    n_verify = sum(1 for k in warmed
                   if isinstance(k, tuple) and k and k[0] == "verify")
    print(f"warmup [{args.kv_layout}, slots={slots}]: decode + "
          f"{len(warmed) - 1 - n_verify} prefill buckets"
          f"{f' + {n_verify} verify' if n_verify else ''} "
          f"pre-compiled in {time.monotonic() - t0:.2f} s")
    return eng


def _stream_digest(streams):
    import hashlib
    return hashlib.sha256(repr(sorted(streams)).encode()).hexdigest()


def run_gen_point(eng, qps: float, duration: float,
                  rng: np.random.RandomState, args) -> tuple:
    """One generation operating point: open-loop prompt arrivals; TTFT
    and per-user tokens/sec come from the engine-stamped result dicts
    (submit → first token / first → last token). ``--prefix-tokens N``
    prepends a fixed N-token system prompt to every request (the
    traffic-class shape ``--prefix-reuse`` amortizes).

    Multi-tenant runs (``--adapters N``) draw each arrival's tenant from
    the ``--adapter-mix`` weights with a DEDICATED selection RNG and its
    prompt from a per-tenant seeded RNG — so tenant ``t``'s k-th request
    is identical in every run of the same knobs, whatever the other
    tenants did. ``--adapter-only t`` replays the same schedule but
    submits only ``t``'s requests: the single-tenant reference whose
    per-tenant digest a mixed run must match. Returns
    ``(row, streams_by_tenant)``."""
    from horovod_tpu.exceptions import (DeadlineExceededError,
                                        ServerOverloadedError)
    gen0 = eng.stats().get("generation") or {}
    n = max(1, int(qps * duration))
    period = 1.0 / qps
    # Deterministic across runs and independent of the arrival RNG, so
    # reuse-on vs reuse-off runs see the SAME system prompt.
    # --prefix-count rotates round-robin over K distinct prefixes (the
    # first one keeps the historical seed, so count=1 digests are
    # unchanged); K long prefixes make the registered working set
    # exceed a tight device pool and exercise offload/prefetch.
    sys_prefixes = [np.random.RandomState(1234 if j == 0 else 4100 + j)
                    .randint(1, 255, size=args.prefix_tokens).tolist()
                    for j in range(max(1, args.prefix_count))]
    # --prefix-mix: which arrivals carry the shared system prompt. A
    # DEDICATED seeded RNG, drawn every arrival regardless of the
    # verdict, so the tenant/prompt streams (and their digests) are
    # identical across mix settings.
    mix_rng = np.random.RandomState(97)
    tenants, weights = _bench_tenants(args)
    # Tenant selection and per-tenant prompts ride their own RNGs; the
    # base-only path keeps drawing prompts from the caller's rng so the
    # single-tenant digests of existing ci legs are unchanged.
    pick_rng = np.random.RandomState(4321)
    prompt_rngs = ({"base": rng} if len(tenants) == 1
                   else {t: np.random.RandomState(7000 + i)
                         for i, t in enumerate(tenants)})
    handles = []
    overload = 0
    sent_by_tenant = {t: 0 for t in tenants}
    shared_sent = 0
    seen_prefixes = set()
    start = time.monotonic()
    for i in range(n):
        delay = start + i * period - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = (tenants[0] if len(tenants) == 1
             else tenants[pick_rng.choice(len(tenants), p=weights)])
        trng = prompt_rngs[t]
        draw = mix_rng.random_sample()
        shared = args.prefix_tokens > 0 and draw < args.prefix_mix
        pfx_idx = shared_sent % len(sys_prefixes)
        if shared:
            shared_sent += 1
        prompt = (sys_prefixes[pfx_idx] if shared else []) + trng.randint(
            1, 255, size=trng.randint(4, 17)).tolist()
        if args.adapter_only and t != args.adapter_only:
            continue        # reference run: same schedule, one tenant
        # Hit-vs-cold TTFT split: the FIRST shared-prefix arrival of
        # the point pays the cold prefill (it registers the prefix);
        # later shared arrivals should prefill only their suffix. A
        # second operating point on the same engine inherits the
        # registry, so its "cold" sample is really a hit — the split is
        # a smoke number; prefix_hit_rate is the precise check.
        cls = "cold"
        if shared:
            cls = "cold" if pfx_idx not in seen_prefixes else "hit"
            seen_prefixes.add(pfx_idx)
        sent_by_tenant[t] += 1
        try:
            kw = {} if t == "base" else {"adapter": t}
            if args.temperature > 0:
                # Seeded sampling: the seed is a pure function of the
                # tenant and its arrival index, so the k-th request of
                # tenant t samples the SAME stream in every run of the
                # same knobs — sampled digests stay as comparable across
                # runs (and across failover replays) as greedy ones.
                from horovod_tpu.serve import SamplingParams
                kw["sampling"] = SamplingParams(
                    temperature=args.temperature, top_k=args.top_k,
                    seed=9000 + 131 * tenants.index(t) + sent_by_tenant[t])
            handles.append((t, cls, eng.submit(prompt, **kw)))
        except ServerOverloadedError:
            overload += 1
    ttft_ms, tps_user, tokens_out = [], [], 0
    ttft_cls = {"hit": [], "cold": []}
    expired, failed = 0, 0
    streams = []
    streams_by_tenant = {t: [] for t in tenants}
    done_by_tenant = {t: 0 for t in tenants}
    ttft_by_tenant = {t: [] for t in tenants}
    for t, cls, h in handles:
        try:
            r = h.result(timeout=120)
            ttft_ms.append(r["ttft_ms"])
            ttft_by_tenant[t].append(r["ttft_ms"])
            ttft_cls[cls].append(r["ttft_ms"])
            tokens_out += r["n_tokens"]
            streams.append(tuple(r["tokens"]))
            streams_by_tenant[t].append(tuple(r["tokens"]))
            done_by_tenant[t] += 1
            if r["tokens_per_sec"] is not None:
                tps_user.append(r["tokens_per_sec"])
        except DeadlineExceededError:
            expired += 1
        except Exception:
            failed += 1
    wall = time.monotonic() - start
    snap = eng.stats()
    # Completion-order-free digest of every completed stream: identical
    # prompts + greedy sampling must give an identical digest whatever
    # the batch composition was — the ci.sh prefix-reuse leg pins
    # reuse-on == reuse-off through this field.
    digest = _stream_digest(streams)
    gen = snap["generation"]
    row = {
        "qps_target": qps,
        # The requests actually SUBMITTED (an --adapter-only reference
        # run skips other tenants' arrivals by design).
        "sent": sum(sent_by_tenant.values()),
        "completed": len(ttft_ms),
        "ttft_p50_ms": _percentile(ttft_ms, 0.50),
        "ttft_p99_ms": _percentile(ttft_ms, 0.99),
        "tokens_per_sec": tokens_out / wall,
        "tps_user_p50": _percentile(tps_user, 0.50),
        "overload_drops": overload,
        "deadline_drops": expired,
        "failed": failed,
        "slot_fill": snap["batch_fill_ratio"],
        # Capacity / memory telemetry (the fixed-HBM claims):
        "kv_layout": snap["kv_layout"],
        "max_slots": snap["max_slots"],
        "max_len": snap["max_len"],
        "cache_bytes": getattr(eng, "bench_cache_bytes", None),
        "peak_concurrent_streams": snap["peak_active_slots"],
        # The children hold the chips; the parent asks no device.
        "peak_bytes_per_chip": (None if args.replica_procs
                                else _peak_bytes_per_chip()),
        "rejected_slots_full": snap["rejected_slots_full"],
        "rejected_blocks_exhausted": snap["rejected_blocks_exhausted"],
        "prefix_hits_total": gen["prefix_hits_total"],
        "prefix_misses_total": gen["prefix_misses_total"],
        "prefix_hit_blocks_total": gen["prefix_hit_blocks_total"],
        # KV memory hierarchy (chunked prefill + host tier): the
        # per-point hit rate from the counter DELTAS (the cumulative
        # totals above smear points), the hit-vs-cold TTFT split of
        # THIS point's completed requests, and the tier traffic. None
        # where a class saw no completion (json-clean, never NaN).
        "prefix_mix": args.prefix_mix,
        "prefix_count": max(1, args.prefix_count),
        "prefix_hit_rate": (
            lambda h, m: (h / (h + m)) if (h + m) > 0 else None)(
                gen["prefix_hits_total"]
                - gen0.get("prefix_hits_total", 0),
                gen["prefix_misses_total"]
                - gen0.get("prefix_misses_total", 0)),
        "ttft_hit_p50_ms": (_percentile(ttft_cls["hit"], 0.50)
                            if ttft_cls["hit"] else None),
        "ttft_cold_p50_ms": (_percentile(ttft_cls["cold"], 0.50)
                             if ttft_cls["cold"] else None),
        "chunked_prefill": bool(snap.get("chunked_prefill", False)),
        "host_blocks": args.host_blocks,
        "kv_offload_blocks_total": gen.get("kv_offload_blocks_total", 0),
        "kv_prefetch_blocks_total": gen.get("kv_prefetch_blocks_total", 0),
        "prefill_chunks_total": gen.get("prefill_chunks_total", 0),
        "prefill_chunks_skipped_total":
            gen.get("prefill_chunks_skipped_total", 0),
        "last_prefill_bucket": snap.get("last_prefill_bucket"),
        "stream_digest": digest,
        # Multi-tenant adapter fields — stamped in EVERY generate row
        # (zeros/base-only when --adapters is off) so a consumer never
        # key-errors across operating modes.
        "adapters": args.adapters,
        "adapter_mix": dict(zip(tenants, weights)),
        "adapter_only": args.adapter_only or None,
        # Traffic shape + injected faults + replica topology, so a
        # digest-bearing row is self-describing about what produced it
        # (cross-topology digest comparison = grep topology + digest).
        "temperature": args.temperature,
        "chaos": args.chaos or None,
        "topology": "process" if args.replica_procs else "thread",
        "tenant_sent": sent_by_tenant,
        "tenant_completed": done_by_tenant,
        # Bench-side per-tenant TTFT percentiles (of THIS point's
        # completions — the engine's snapshot percentiles are
        # engine-lifetime and, in fleet mode, per-replica): the numbers
        # the ci.sh starvation drill bounds for the quiet tenant.
        "tenant_ttft_ms": {
            t: {"p50": _percentile(xs, 0.50), "p99": _percentile(xs, 0.99)}
            for t, xs in ttft_by_tenant.items() if xs},
        "stream_digests": {t: _stream_digest(s)
                           for t, s in streams_by_tenant.items()},
        "rejected_tenant_quota": snap.get("rejected_tenant_quota", 0),
        "tenants": snap.get("tenants") or {},
        # SLO-aware multi-tenancy fields — stamped in EVERY generate row
        # (zeros / empty maps when the knobs are off) so consumers never
        # key-error across modes. Preemption counters are cumulative
        # over the engine's life, like the prefix counters above.
        "tenant_weights": args.tenant_weights_map or {},
        "priority_mix": args.priority_mix_map or {},
        "tenant_slo_ms": args.tenant_slo_ms_map or {},
        "preemptions": gen.get("preemptions_total", 0),
        "preempt_resumed": gen.get("preempt_resumed_total", 0),
        "preempt_exhausted": gen.get("preempt_exhausted_total", 0),
        # Speculative-decoding fields — stamped in EVERY generate row
        # (k=0 / None ratios when --spec-k is off) so consumers never
        # key-error across modes. Cumulative over the engine's life,
        # like the prefix counters above.
        "spec_k": int(snap.get("spec_k") or 0),
        "spec_accept_rate": (snap.get("spec") or {}).get("accept_rate"),
        "tokens_per_step": (snap.get("spec") or {}).get("tokens_per_step"),
    }
    if snap.get("adapters_resident") is not None:
        row["adapters_resident"] = snap["adapters_resident"]
    if snap["kv_layout"] == "paged" and "block_size" in snap:
        row["block_size"] = snap["block_size"]
        row["blocks"] = snap.get("blocks")
    if "fleet" in snap:
        # Fleet rows: membership and the autoscaler's decisions AT ROW
        # END (cumulative), so a spike row shows the grow it caused.
        row["replicas_ready"] = snap["fleet"]["n_ready"]
        row["replicas"] = snap["fleet"]["replicas"]
        row["scale_events"] = snap["fleet"]["scale_events"]
        row["dispatch"] = snap["fleet"]["dispatch_total"]
        row["failover"] = snap["fleet"]["failover_total"]
        row["stranded"] = snap["fleet"]["streams_stranded_total"]
        if "adapter_dispatch" in snap["fleet"]:
            row["adapter_dispatch"] = snap["fleet"]["adapter_dispatch"]
        if "prefix_dispatch" in snap["fleet"]:
            row["prefix_dispatch"] = snap["fleet"]["prefix_dispatch"]
    return row, streams_by_tenant


def run_point(eng, qps: float, duration: float, rng: np.random.RandomState,
              item_shape) -> dict:
    """Drive one operating point; returns its row of the curve."""
    from horovod_tpu.exceptions import (DeadlineExceededError,
                                        ServerOverloadedError)
    snap0 = eng.stats()
    n = max(1, int(qps * duration))
    period = 1.0 / qps
    futures = []
    overload = 0
    start = time.monotonic()
    for i in range(n):
        due = start + i * period
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        x = rng.randn(*item_shape).astype(np.float32)
        try:
            fut = eng.submit(x)
            # Stamp completion ON the done callback — collecting results
            # after the send loop would otherwise credit early responses
            # with the whole send phase's wall time.
            fut.t_done = None
            fut.add_done_callback(
                lambda f, t=time.monotonic: setattr(f, "t_done", t()))
            futures.append((fut, time.monotonic()))
        except ServerOverloadedError:
            overload += 1
    lat_ms, expired, failed = [], 0, 0
    for fut, t_sub in futures:
        try:
            fut.result(timeout=60)
            # result() can return a hair before the done callback fires
            # (set_result notifies waiters under the lock, runs callbacks
            # after releasing it) — give the stamp a moment before
            # falling back to now (the fallback smears by microseconds).
            for _ in range(1000):
                if fut.t_done is not None:
                    break
                time.sleep(0)
            lat_ms.append(((fut.t_done or time.monotonic()) - t_sub) * 1e3)
        except DeadlineExceededError:
            expired += 1
        except Exception:
            failed += 1
    wall = time.monotonic() - start
    snap = eng.stats()
    d_rows = snap["batch_rows_total"] - snap0["batch_rows_total"]
    d_live = (snap["batch_live_rows_total"]
              - snap0["batch_live_rows_total"])
    return {
        "qps_target": qps,
        "qps_achieved": len(lat_ms) / wall,
        "sent": n,
        "completed": len(lat_ms),
        "p50_ms": _percentile(lat_ms, 0.50),
        "p99_ms": _percentile(lat_ms, 0.99),
        "overload_drops": overload,
        "deadline_drops": expired,
        "failed": failed,
        "batch_fill": (d_live / d_rows) if d_rows else None,
    }


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--mode", choices=("predict", "generate"),
                   default="predict",
                   help="predict: single-shot Engine; generate: the "
                        "continuous-batching GenerationEngine")
    p.add_argument("--qps", default="200",
                   help="target request rate; comma-separate for a curve")
    p.add_argument("--duration", type=float, default=5.0,
                   help="seconds per operating point")
    p.add_argument("--features", type=int, default=64)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--batch-timeout-ms", type=float, default=5.0)
    p.add_argument("--max-queue", type=int, default=512)
    p.add_argument("--deadline-ms", type=float, default=1000.0,
                   help="per-request deadline (0 disables)")
    p.add_argument("--slots", type=int, default=8,
                   help="[generate] concurrent decode slots")
    p.add_argument("--max-len", type=int, default=128,
                   help="[generate] KV-cache depth (prompt + generated)")
    p.add_argument("--gen-tokens", type=int, default=16,
                   help="[generate] tokens generated per request")
    p.add_argument("--kv-layout", choices=("contiguous", "paged"),
                   default="contiguous",
                   help="[generate] KV-cache layout: per-slot max_len "
                        "reservation vs block-table paging")
    p.add_argument("--block-size", type=int, default=16,
                   help="[generate, paged] positions per KV block")
    p.add_argument("--n-blocks", type=int, default=0,
                   help="[generate, paged] pool size incl. the trash "
                        "block (0 = match the contiguous footprint)")
    p.add_argument("--prefix-reuse", action="store_true",
                   help="[generate, paged] share full block-aligned "
                        "prompt prefixes copy-on-write")
    p.add_argument("--paged-kernel", action="store_true",
                   help="[generate, paged] Pallas paged decode-attention "
                        "kernel where supported")
    p.add_argument("--prefix-tokens", type=int, default=0,
                   help="[generate] fixed system-prompt tokens prepended "
                        "to every request (the prefix-reuse traffic "
                        "shape)")
    p.add_argument("--model-dim", type=int, default=0,
                   help="override the bench model width (d_ff = 2*dim; "
                        "0 keeps the default tiny model). Wider models "
                        "put the bench in the regime where prefill "
                        "compute dominates KV block copies")
    p.add_argument("--prefix-count", type=int, default=1,
                   help="number of distinct shared system prefixes rotated "
                        "round-robin across shared arrivals. >1 grows the "
                        "registered-prefix working set past a tight device "
                        "pool so the host tier's offload/prefetch path runs")
    p.add_argument("--prefix-mix", type=float, default=1.0,
                   help="[generate, --prefix-tokens] fraction of "
                        "requests carrying the shared system prompt "
                        "(default 1.0 = all, the old behavior); the JSON "
                        "row stamps the per-point prefix hit rate and "
                        "the hit-vs-cold TTFT split")
    p.add_argument("--chunked-prefill", action="store_true",
                   help="[generate, paged, --prefix-reuse] chunked "
                        "prefill: the compiled program starts at the "
                        "first non-shared block, reading hit blocks' "
                        "K/V from the pool instead of recomputing "
                        "(docs/inference.md 'KV memory hierarchy')")
    p.add_argument("--chunk-blocks", type=int, default=1,
                   help="[generate, --chunked-prefill] blocks per "
                        "prefill scan chunk (power of two)")
    p.add_argument("--host-blocks", type=int, default=0,
                   help="[generate, paged, --prefix-reuse] host-tier "
                        "block pool: cold registered-prefix blocks "
                        "offload to pinned host memory and prefetch "
                        "back at admission (0 = device-only)")
    p.add_argument("--host-admission", choices=("wait", "miss"),
                   default="wait",
                   help="[generate, --host-blocks] admission policy "
                        "while a host-tier prefetch is in flight: wait "
                        "(hold the request for the full hit) or miss "
                        "(admit now, recompute the prefix)")
    p.add_argument("--adapters", type=int, default=0,
                   help="[generate] seeded LoRA fine-tunes (tenants "
                        "a0..aN-1) loaded next to the base model; every "
                        "JSON row then stamps the per-tenant fields "
                        "(docs/inference.md 'Multi-tenant adapters')")
    p.add_argument("--adapter-rank", type=int, default=4,
                   help="[generate, --adapters] LoRA rank of the bench "
                        "fine-tunes")
    p.add_argument("--adapter-mix", default="",
                   help="[generate, --adapters] comma-separated arrival "
                        "weights, base first then a0..aN-1 (default "
                        "uniform)")
    p.add_argument("--adapter-only", default="",
                   help="[generate, --adapters] replay the same arrival "
                        "schedule submitting ONLY this tenant's requests "
                        "(base|aK) — the single-tenant digest reference "
                        "the ci.sh multi-tenant drill compares against")
    p.add_argument("--tenant-weights", default="",
                   help="[generate] fair-scheduling weights as "
                        "tenant:weight pairs, e.g. 'base:1,a0:4' — a0 "
                        "then gets ~4x base's decode admissions under "
                        "contention (docs/inference.md 'Fair "
                        "scheduling, budgets, and preemption')")
    p.add_argument("--priority-mix", default="",
                   help="[generate] strict priority classes as "
                        "tenant:priority pairs, e.g. 'a0:1' — higher "
                        "classes admit first and may preempt lower "
                        "(unnamed tenants are class 0)")
    p.add_argument("--tenant-slo-ms", default="",
                   help="[generate] per-tenant TTFT SLO targets as "
                        "tenant:ms pairs, e.g. 'base:500,a0:150' — "
                        "misses burn the hvd_tenant_slo_* series and "
                        "steer SLO-aware fleet dispatch")
    p.add_argument("--preempt-retries", type=int, default=3,
                   help="[generate] evictions a stream survives before "
                        "preempted_exhausted (GenerationConfig."
                        "preempt_retries); the ci.sh preemption drill "
                        "raises it so a digest-pinned run can never "
                        "fail on an unlucky eviction streak")
    p.add_argument("--replicas", type=int, default=1,
                   help="[generate] engine replicas behind one "
                        "FleetRouter (static fleet; with --autoscale "
                        "this is the GROW CEILING instead)")
    p.add_argument("--replica-procs", action="store_true",
                   help="[generate] run each fleet replica as a "
                        "SUBPROCESS worker (python -m horovod_tpu.serve."
                        "proc_replica) behind a ProcReplicaClient, "
                        "instead of an in-process engine thread — the "
                        "same seeded traffic then exercises the serving "
                        "plane across a real process boundary; every "
                        "JSON row stamps topology: 'process' so digest "
                        "comparisons across topologies are one grep "
                        "(docs/inference.md 'Process replicas')")
    p.add_argument("--autoscale", action="store_true",
                   help="[generate] start at --min-replicas and let the "
                        "queue-depth FleetAutoscaler grow/shrink the "
                        "fleet between --min-replicas and --replicas "
                        "(docs/inference.md 'Serving fleet')")
    p.add_argument("--min-replicas", type=int, default=1,
                   help="[generate, --autoscale] fleet floor")
    p.add_argument("--scale-high", type=float, default=4.0,
                   help="[generate, --autoscale] grow watermark: queued "
                        "work per ready replica")
    p.add_argument("--scale-low", type=float, default=0.5,
                   help="[generate, --autoscale] shrink watermark")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="[generate] sampling temperature (0 = greedy); "
                        ">0 switches every request to seeded sampling "
                        "with a per-(tenant, arrival-index) seed, so "
                        "stream digests stay run-to-run comparable")
    p.add_argument("--top-k", type=int, default=0,
                   help="[generate, --temperature>0] top-k cutoff "
                        "(0 = full vocab)")
    p.add_argument("--spec-k", type=int, default=0,
                   help="[generate] speculative decoding: draft up to K "
                        "tokens per decode step with the self-speculative "
                        "n-gram drafter and score them in one verify "
                        "forward (0 = off). Greedy streams stay digest-"
                        "identical to a spec-off run; needs the gather "
                        "decode path (incompatible with --paged-kernel)")
    p.add_argument("--chaos", default="",
                   help="[generate] serving-plane HVD_FAULT_SPEC clause(s) "
                        "armed for this run, e.g. "
                        "'replica_kill=r1@stream=3' — the deterministic-"
                        "failover drill knob (docs/fault_tolerance.md "
                        "'Serving failures')")
    p.add_argument("--cache-mb", type=float, default=0,
                   help="[generate] fixed KV-cache byte budget; derives "
                        "slots (contiguous) or pool+slots (paged) — the "
                        "equal-bytes capacity comparison (0 = use "
                        "--slots)")
    p.add_argument("--json", default="",
                   help="[generate] append one JSON line per operating "
                        "point to this file")
    args = p.parse_args()
    if args.deadline_ms == 0:
        args.deadline_ms = None
    if args.replicas < 1:
        p.error("--replicas must be >= 1")
    if args.min_replicas < 1:
        p.error("--min-replicas must be >= 1 (a fleet of zero serves "
                "nothing)")
    if args.autoscale and args.min_replicas > args.replicas:
        p.error("--min-replicas must be <= --replicas (the grow ceiling)")
    if args.adapters < 0:
        p.error("--adapters must be >= 0")
    if args.adapters and args.mode != "generate":
        p.error("--adapters applies to --mode generate only")
    if args.replica_procs and args.mode != "generate":
        p.error("--replica-procs applies to --mode generate only")
    if args.spec_k < 0:
        p.error("--spec-k must be >= 0 (0 = speculation off)")
    if args.spec_k:
        if args.mode != "generate":
            p.error("--spec-k applies to --mode generate only")
        if args.paged_kernel:
            p.error("--spec-k needs the gather decode path: drop "
                    "--paged-kernel (the Pallas kernel is allclose-"
                    "pinned, not bitwise, so it cannot honor the "
                    "spec-off greedy digest contract)")
    if args.temperature < 0:
        p.error("--temperature must be >= 0 (0 = greedy)")
    if args.top_k < 0:
        p.error("--top-k must be >= 0 (0 = full vocab)")
    if args.chaos:
        if args.mode != "generate":
            p.error("--chaos applies to --mode generate only (serving-"
                    "plane clauses fire inside the generation engine "
                    "loop)")
        from horovod_tpu.testing import faults
        try:
            clauses = faults.parse_spec(args.chaos)
        except faults.FaultSpecError as e:
            p.error(str(e))
        if not any(f.target == "serve" for f in clauses):
            p.error(f"--chaos {args.chaos!r} has no serving-plane clause "
                    f"(replica_kill= / replica_hang= / "
                    f"replica_proc_kill= / slow_step=) — training-plane "
                    f"drills belong to tpurun, not the bench")
        if any(f.action == "replica_proc_kill" for f in clauses) \
                and not args.replica_procs:
            # In a thread fleet the clause would fire inside THIS
            # process's engine loop and SIGKILL the whole bench — the
            # drill only means anything when the victim is a child.
            p.error("--chaos replica_proc_kill needs --replica-procs: "
                    "the clause SIGKILLs the replica's own PROCESS, "
                    "which in a thread fleet is the bench itself")
        if any(f.action in ("replica_kill", "replica_hang",
                            "replica_proc_kill")
               for f in clauses) \
                and args.replicas <= 1 and not args.autoscale:
            # A bare engine's serve_name stays "engine" — a clause
            # targeting r0/r1 could never fire, and the run would read
            # as a passed drill that never drilled anything.
            p.error("--chaos replica_kill/replica_hang needs a fleet "
                    "(--replicas >= 2 or --autoscale): replica names "
                    "are stamped by the FleetRouter, and a kill drill "
                    "without a surviving replica has nothing to fail "
                    "over to")
        # Armed via the one env knob every injection rides — the engine
        # loops read it, so this must land BEFORE engines are built.
        os.environ["HVD_FAULT_SPEC"] = args.chaos
        faults.reset()
    if args.adapter_mix and not args.adapters:
        p.error("--adapter-mix needs --adapters N")
    if not 0.0 <= args.prefix_mix <= 1.0:
        p.error("--prefix-mix must be in [0, 1]")
    if args.model_dim and (args.model_dim < 4 or args.model_dim % 4):
        p.error("--model-dim must be a positive multiple of 4 (the "
                "bench model has 4 heads)")
    if args.prefix_count < 1:
        p.error("--prefix-count must be >= 1")
    if args.prefix_count > 1 and not args.prefix_tokens:
        p.error("--prefix-count > 1 needs --prefix-tokens N")
    if args.prefix_mix != 1.0:
        if args.mode != "generate":
            p.error("--prefix-mix applies to --mode generate only")
        if not args.prefix_tokens:
            p.error("--prefix-mix needs --prefix-tokens N (without a "
                    "shared system prompt there is nothing to mix)")
    if args.chunked_prefill or args.host_blocks:
        what = "--chunked-prefill" if args.chunked_prefill \
            else "--host-blocks"
        if args.mode != "generate" or args.kv_layout != "paged":
            p.error(f"{what} needs --mode generate --kv-layout paged")
        if not args.prefix_reuse:
            p.error(f"{what} needs --prefix-reuse (its whole point is "
                    f"the prefix cache)")
    if args.chunk_blocks < 1:
        p.error("--chunk-blocks must be >= 1")
    if args.host_blocks < 0:
        p.error("--host-blocks must be >= 0")
    try:
        args.tenant_weights_map = _parse_tenant_map(
            args.tenant_weights, "--tenant-weights", float)
        args.priority_mix_map = _parse_tenant_map(
            args.priority_mix, "--priority-mix", int)
        args.tenant_slo_ms_map = _parse_tenant_map(
            args.tenant_slo_ms, "--tenant-slo-ms", float)
    except SystemExit as e:
        p.error(str(e))
    if (args.tenant_weights_map or args.priority_mix_map
            or args.tenant_slo_ms_map) and args.mode != "generate":
        p.error("--tenant-weights/--priority-mix/--tenant-slo-ms apply "
                "to --mode generate only")
    if args.mode == "generate":
        try:
            # ONE naming/weights rule — the same call the run schedule
            # uses; fail fast, before model build + warmup.
            tenants, _ = _bench_tenants(args)
        except SystemExit as e:
            p.error(str(e))
        if args.adapter_only and args.adapter_only not in tenants:
            p.error(f"--adapter-only must be one of {tenants} "
                    f"(set --adapters first)")
        for what, m in (("--tenant-weights", args.tenant_weights_map),
                        ("--priority-mix", args.priority_mix_map),
                        ("--tenant-slo-ms", args.tenant_slo_ms_map)):
            bad = [t for t in m if t not in tenants]
            if bad:
                p.error(f"{what} names unknown tenant(s) {bad} — this "
                        f"run's tenants are {tenants} (set --adapters)")
    elif args.adapter_only:
        p.error("--adapter-only applies to --mode generate only")

    if args.mode == "generate":
        run_generate(args)
        return

    eng = _build_engine(args)
    rng = np.random.RandomState(0)
    points = [float(q) for q in str(args.qps).split(",")]
    hdr = (f"{'qps→':>8}{'qps':>9}{'p50 ms':>9}{'p99 ms':>9}"
           f"{'fill':>7}{'overload':>10}{'deadline':>10}")
    print(hdr)
    dropped_in_deadline = 0
    for q in points:
        row = run_point(eng, q, args.duration, rng, (args.features,))
        # Overload rejects and execution failures hit requests that were
        # still within deadline — the drops the gate counts. Deadline
        # expiries are the contract working as specified, reported but
        # not gated.
        dropped_in_deadline += row["overload_drops"] + row["failed"]
        fill = row["batch_fill"]
        print(f"{row['qps_target']:>8.0f}{row['qps_achieved']:>9.1f}"
              f"{row['p50_ms']:>9.2f}{row['p99_ms']:>9.2f}"
              f"{(fill if fill is not None else 0):>7.2f}"
              f"{row['overload_drops']:>10}{row['deadline_drops']:>10}")
        if not (np.isfinite(row["p50_ms"]) and np.isfinite(row["p99_ms"])):
            print("FAIL: empty latency report (no request completed)")
            eng.shutdown(drain=False)
            sys.exit(1)
    eng.shutdown()
    if dropped_in_deadline:
        print(f"FAIL: {dropped_in_deadline} in-deadline requests dropped")
        sys.exit(1)
    print("SERVE BENCH OK")


def _fleet_settle(eng, args, lost_streams: int, streams_by_tenant=None):
    """The closed loop's back half: traffic has stopped, so the
    autoscaler must DRAIN the extra replicas (finishing every admitted
    stream) and shrink back to the floor. Waits for the membership to
    settle, then returns the fleet summary row the ci.sh drill asserts
    on (grow >= 1, shrink to min, zero lost streams)."""
    scaler = getattr(eng, "bench_autoscaler", None)
    if scaler is not None:      # a static fleet has nothing to shrink
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            c = eng.counts()
            if (c["ready"] <= args.min_replicas and c["warming"] == 0
                    and c["draining"] == 0):
                break
            time.sleep(0.25)
        scaler.stop()
    snap = eng.stats()
    row = {
        "fleet": True,
        "autoscale": bool(args.autoscale),
        "min_replicas": args.min_replicas,
        "max_replicas": args.replicas,
        "ready_final": snap["fleet"]["n_ready"],
        "draining_final": snap["fleet"]["n_draining"],
        "queue_depth_final": snap["queue_depth"],
        "scale_events": snap["fleet"]["scale_events"],
        "dispatch": snap["fleet"]["dispatch_total"],
        "drained_lost_streams": lost_streams,
        # The failover plane's whole-run verdict (ISSUE 15 chaos drill):
        # every stranded stream must be resumed (bit-identically) or
        # counted exhausted — never silently lost.
        "failover": snap["fleet"]["failover_total"],
        "stranded": snap["fleet"]["streams_stranded_total"],
        "chaos": args.chaos or None,
        "topology": "process" if args.replica_procs else "thread",
        "spec_k": int(snap.get("spec_k") or 0),
        "spec_accept_rate": (snap.get("spec") or {}).get("accept_rate"),
        "tokens_per_step": (snap.get("spec") or {}).get("tokens_per_step"),
    }
    if streams_by_tenant is not None:
        # Per-tenant digest map over the WHOLE run (all operating
        # points): the summary-line form of the per-row maps, so a CI
        # drill can compare tenants across whole runs in one line.
        row["stream_digests"] = {t: _stream_digest(s)
                                 for t, s in streams_by_tenant.items()}
    if "adapter_dispatch" in snap["fleet"]:
        row["adapter_dispatch"] = snap["fleet"]["adapter_dispatch"]
    if "prefix_dispatch" in snap["fleet"]:
        row["prefix_dispatch"] = snap["fleet"]["prefix_dispatch"]
    return row


def run_generate(args):
    import json

    eng = _build_gen_engine(args)
    fleet = hasattr(eng, "counts")      # FleetRouter duck-type marker
    rng = np.random.RandomState(0)
    points = [float(q) for q in str(args.qps).split(",")]
    hdr = (f"{'qps→':>8}{'done':>7}{'ttft p50':>10}{'ttft p99':>10}"
           f"{'tok/s':>9}{'tok/s/u':>9}{'fill':>7}{'overload':>10}"
           f"{'deadline':>10}")
    print(hdr)
    dropped_in_deadline = 0
    failed_total = 0
    total_tps = 0.0
    all_streams: dict = {}
    for q in points:
        row, streams_by_tenant = run_gen_point(eng, q, args.duration,
                                               rng, args)
        for t, s in streams_by_tenant.items():
            all_streams.setdefault(t, []).extend(s)
        dropped_in_deadline += row["overload_drops"] + row["failed"]
        failed_total += row["failed"]
        total_tps += row["tokens_per_sec"]
        print(f"{row['qps_target']:>8.0f}{row['completed']:>7}"
              f"{row['ttft_p50_ms']:>10.2f}{row['ttft_p99_ms']:>10.2f}"
              f"{row['tokens_per_sec']:>9.1f}{row['tps_user_p50']:>9.1f}"
              f"{(row['slot_fill'] or 0):>7.2f}"
              f"{row['overload_drops']:>10}{row['deadline_drops']:>10}")
        print(json.dumps(row))
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(row) + "\n")
        if not (np.isfinite(row["ttft_p50_ms"])
                and np.isfinite(row["ttft_p99_ms"])):
            print("FAIL: empty TTFT report (no request completed)")
            eng.shutdown(drain=False)
            sys.exit(1)
    if fleet:
        fleet_row = _fleet_settle(eng, args, failed_total, all_streams)
        print(json.dumps(fleet_row))
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(fleet_row) + "\n")
    if args.spec_k:
        sp = eng.stats().get("spec") or {}
        ar, tps = sp.get("accept_rate"), sp.get("tokens_per_step")
        print(f"spec: k={args.spec_k}"
              f" accept_rate={ar if ar is None else round(ar, 4)}"
              f" tokens_per_step={tps if tps is None else round(tps, 3)}")
    eng.shutdown()
    if dropped_in_deadline:
        print(f"FAIL: {dropped_in_deadline} in-deadline requests dropped")
        sys.exit(1)
    if not total_tps > 0:
        print("FAIL: zero aggregate tokens/sec")
        sys.exit(1)
    print("SERVE BENCH OK")


if __name__ == "__main__":
    main()
