#!/usr/bin/env bash
# CI pipeline (parity: reference .travis.yml — build the native core, run the
# collective test suite under a multi-"rank" world, then shrunken examples
# end-to-end, .travis.yml:77-108).
set -euo pipefail
cd "$(dirname "$0")"

echo "== build native coordination core =="
make -C horovod_tpu/coord

echo "== native core threaded selftest (plain + ThreadSanitizer) =="
make -C horovod_tpu/coord selftest tsan

echo "== unit + multi-process test suite (8-device virtual CPU mesh) =="
# -m 'not slow' mirrors the tier-1 gate: the slow-marked AOT TPU
# cross-compile evidence test takes ~8 min on a CPU host (run
# tests/test_overlap.py directly for it), and the multi-node world-4
# launcher drill is ~70 s of subprocess spawns the np=3 test already
# covers (run tests/test_launcher.py directly). --durations=15 keeps the
# tier-1 wall-budget regression surface visible: the suite must stay
# well under its 870 s cap, so the slowest tests are named on every run.
python -m pytest tests/ -q -m 'not slow' --durations=15

echo "== shrunken examples end-to-end (integration tests) =="
run_cpu() {
  PYTHONPATH= JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 "$@"
}
run_cpu python examples/mnist.py
run_cpu python examples/mnist_estimator.py --steps 32
run_cpu python examples/mnist_advanced.py
run_cpu python examples/cifar10_cnn.py --epochs 1
run_cpu python examples/word2vec.py
run_cpu python examples/transformer_lm.py --dp 2 --sp 2 --tp 2 --steps 12 --seq 64
run_cpu python examples/transformer_lm.py --dp 2 --pp 2 --tp 2 --steps 12 --seq 64
run_cpu python examples/imagenet_resnet50.py --epochs 1 --image 32 --batch-per-chip 4 \
  --ckpt-dir "$(mktemp -d)"

echo "== serving smoke: warm the buckets, 200 QPS for 5 s, assert the drop gate =="
# The serving plane's CI contract (docs/inference.md): the engine must
# pre-compile every bucket, sustain the target rate with mixed batch
# sizes, drop ZERO in-deadline requests, and produce a non-empty p50/p99
# report — serve_bench exits nonzero on any violation.
run_cpu timeout -k 10 180 python bin/serve_bench.py --qps 200 --duration 5

echo "== serving smoke: continuous-batching generation (TTFT + tokens/sec gate) =="
# The generation plane's CI contract (docs/inference.md "Generation"):
# prefill/decode buckets pre-compile, open-loop prompt arrivals sustain
# the rate with slots joining/leaving mid-flight, ZERO in-deadline drops,
# nonzero aggregate tokens/sec, and a non-empty p50/p99 TTFT report —
# serve_bench exits nonzero on any violation.
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 5000
# The slow-marked HTTP /generate drills (chunked streaming, healthz
# lifecycle) run here, outside the tier-1 marker filter.
timeout -k 10 300 python -m pytest tests/test_generate.py -q

echo "== serving smoke: paged KV cache (same gate, block-table layout) =="
# Identical qps/duration/gates as the contiguous generation smoke — the
# paged engine must clear the same bar (docs/inference.md "Paged KV
# cache").
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 5000 --kv-layout paged --block-size 16

echo "== paged capacity: more concurrent streams at EQUAL cache bytes =="
# The ROADMAP item-2 success metric: at a FIXED KV-cache byte budget
# (--cache-mb sizes both layouts from the same budget), a burst of short
# prompts must reach strictly higher peak concurrency on the paged
# engine than on the contiguous one (whose slot count the worst-case
# max_len reservation caps).
rm -f /tmp/hvd_cap_contig.json /tmp/hvd_cap_paged.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 400 --duration 1 --deadline-ms 0 --cache-mb 0.5 --max-len 128 \
  --kv-layout contiguous --json /tmp/hvd_cap_contig.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 400 --duration 1 --deadline-ms 0 --cache-mb 0.5 --max-len 128 \
  --kv-layout paged --block-size 16 --json /tmp/hvd_cap_paged.json
python - <<'PYEOF'
import json
c = json.loads(open("/tmp/hvd_cap_contig.json").read().splitlines()[-1])
p = json.loads(open("/tmp/hvd_cap_paged.json").read().splitlines()[-1])
assert c["cache_bytes"] == p["cache_bytes"], (c["cache_bytes"],
                                              p["cache_bytes"])
print(f"capacity @ {c['cache_bytes']} cache bytes: contiguous peak "
      f"{c['peak_concurrent_streams']} (slots {c['max_slots']}), paged "
      f"peak {p['peak_concurrent_streams']} (slots {p['max_slots']})")
assert p["peak_concurrent_streams"] > c["peak_concurrent_streams"], \
    "paged engine must sustain MORE concurrent streams at equal cache bytes"
print("PAGED CAPACITY OK")
PYEOF

echo "== prefix reuse: nonzero hits, bit-identical streams vs no-reuse =="
# Same seeded prompt mix (16-token shared system prefix) with reuse on
# vs off: the reuse run must actually HIT the prefix cache, and the
# completion-order-free digest of every greedy stream must be identical
# — sharing saves memory, never changes a token.
rm -f /tmp/hvd_px_on.json /tmp/hvd_px_off.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --kv-layout paged --block-size 16 \
  --prefix-tokens 16 --prefix-reuse --json /tmp/hvd_px_on.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --kv-layout paged --block-size 16 \
  --prefix-tokens 16 --json /tmp/hvd_px_off.json
python - <<'PYEOF'
import json
on = json.loads(open("/tmp/hvd_px_on.json").read().splitlines()[-1])
off = json.loads(open("/tmp/hvd_px_off.json").read().splitlines()[-1])
assert on["completed"] == on["sent"] and off["completed"] == off["sent"]
assert on["prefix_hits_total"] > 0, "prefix cache never hit"
assert off["prefix_hits_total"] == 0
assert on["stream_digest"] == off["stream_digest"], \
    "prefix sharing changed a token stream"
print(f"prefix reuse: {on['prefix_hits_total']} hits, digests identical")
print("PREFIX REUSE OK")
PYEOF

echo "== KV hierarchy: one digest across cold / hit / host-tier / disaggregated legs =="
# ISSUE 18 acceptance, bit-identity at every tier: ONE seeded workload
# (3 rotating 96-token system prefixes, 60% shared traffic) replayed
# against four shapes of the SAME chunked-prefill program — ample pool
# (high hit rate, suffix-sized prefills), starved pool (chains
# reclaimed every admission -> every arrival cold), tight pool + host
# tier (chains survive by offload/prefetch roundtrip), and a 2-process
# disaggregated fleet with prefix-affine dispatch. Every leg completes
# everything; every leg emits the IDENTICAL stream digest.
KVH="--mode generate --qps 20 --duration 5 --deadline-ms 0"
KVH="$KVH --kv-layout paged --block-size 16 --prefix-tokens 96"
KVH="$KVH --prefix-count 3 --gen-tokens 16 --prefix-reuse"
KVH="$KVH --chunked-prefill --prefix-mix 0.6"
rm -f /tmp/hvd_kvh_hit.json /tmp/hvd_kvh_cold.json \
      /tmp/hvd_kvh_tier.json /tmp/hvd_kvh_fleet.json
run_cpu timeout -k 10 240 python bin/serve_bench.py $KVH \
  --json /tmp/hvd_kvh_hit.json
run_cpu timeout -k 10 240 python bin/serve_bench.py $KVH \
  --n-blocks 12 --json /tmp/hvd_kvh_cold.json
run_cpu timeout -k 10 240 python bin/serve_bench.py $KVH \
  --n-blocks 20 --host-blocks 64 --json /tmp/hvd_kvh_tier.json
run_cpu timeout -k 10 300 python bin/serve_bench.py $KVH \
  --replicas 2 --replica-procs --json /tmp/hvd_kvh_fleet.json
python - <<'PYEOF'
import json

def rows(path):
    return [json.loads(l) for l in open(path).read().splitlines()]

hit = rows("/tmp/hvd_kvh_hit.json")[-1]
cold = rows("/tmp/hvd_kvh_cold.json")[-1]
tier = rows("/tmp/hvd_kvh_tier.json")[-1]
frows = rows("/tmp/hvd_kvh_fleet.json")
fpt = [r for r in frows if "stream_digest" in r][-1]
fleet = [r for r in frows if r.get("fleet") is True][-1]
for leg in (hit, cold, tier, fpt):
    assert leg["completed"] == leg["sent"], (leg["completed"], leg["sent"])
# The tentpole in one line: four legs, four hit depths and tiers, ONE
# digest — a prefix hit, a host roundtrip, or a remote replica may
# change WHERE tokens come from, never which tokens.
digests = {d["stream_digest"] for d in (hit, cold, tier, fpt)}
assert len(digests) == 1, digests
# Hit leg really skips prefix-hit compute (suffix-sized programs).
assert hit["prefix_hit_rate"] > 0.5, hit["prefix_hit_rate"]
assert hit["prefill_chunks_skipped_total"] > 0
assert hit["ttft_hit_p50_ms"] is not None
assert hit["ttft_cold_p50_ms"] is not None
# Cold leg: the starved pool reclaims every chain, so nothing hits and
# every chunk is computed — strictly more prefill work, same digest.
assert cold["prefix_hit_rate"] < 0.2, cold["prefix_hit_rate"]
assert cold["prefill_chunks_skipped_total"] == 0, cold
assert cold["prefill_chunks_total"] > hit["prefill_chunks_total"]
# Tier leg: blocks actually moved host-ward AND back, books balanced.
assert tier["kv_offload_blocks_total"] > 0, tier
assert tier["kv_prefetch_blocks_total"] > 0, tier
assert tier["prefix_hit_rate"] > 0.5, tier["prefix_hit_rate"]
b = tier["blocks"]
assert b["free"] + b["used"] == b["total"], b
assert b["host_used"] + b["host_free"] == b["host_total"], b
# Disaggregated leg: the router sorted prefix-holding replicas first.
pd = fleet.get("prefix_dispatch") or {}
assert pd.get("affine", 0) > 0, fleet
print(f"hit leg: hit_rate {hit['prefix_hit_rate']:.2f}, "
      f"{hit['prefill_chunks_skipped_total']} chunks skipped, ttft "
      f"hit/cold p50 {hit['ttft_hit_p50_ms']:.2f}/"
      f"{hit['ttft_cold_p50_ms']:.2f} ms")
print(f"cold leg: {cold['prefill_chunks_total']} chunks computed "
      f"(hit leg {hit['prefill_chunks_total']})")
print(f"tier leg: offload {tier['kv_offload_blocks_total']} / prefetch "
      f"{tier['kv_prefetch_blocks_total']} blocks, hit_rate "
      f"{tier['prefix_hit_rate']:.2f}")
print(f"fleet leg: prefix_dispatch {pd}")
print("KV HIERARCHY DIGESTS OK")
PYEOF

echo "== KV hierarchy: host tier raises effective capacity under chain thrash =="
# ISSUE 18 acceptance, capacity: two 96-token prefix chains rotate
# through a device pool that holds only ONE (11 usable blocks), with
# prefill-bound traffic at d_model 512 — the regime the hierarchy is
# built for, where chunk compute dominates block copies — and a tiny
# admission queue. Device-only: each admission reclaims (DESTROYS) the
# other chain, nearly every arrival prefills cold holding a private
# full-length chain, the queue backs up, blocks_exhausted rejections
# pile up. Host-tiered: the same pressure OFFLOADS the chain, the next
# arrival prefetches it back and hits — strictly fewer rejections and
# more completions from the very same device pool.
KVC="--mode generate --qps 80 --duration 5 --deadline-ms 0"
KVC="$KVC --kv-layout paged --block-size 16 --slots 4 --n-blocks 12"
KVC="$KVC --max-queue 8 --model-dim 512 --prefix-tokens 96"
KVC="$KVC --prefix-count 2 --gen-tokens 1 --prefix-reuse"
KVC="$KVC --chunked-prefill --prefix-mix 1.0"
rm -f /tmp/hvd_kvc_tier.json /tmp/hvd_kvc_dev.json
# Both legs overload by design (rejections are the measurement), and
# serve_bench exits nonzero on drops — the verdict lives in the
# assertions below, not the exit codes.
run_cpu timeout -k 10 240 python bin/serve_bench.py $KVC \
  --host-blocks 16 --json /tmp/hvd_kvc_tier.json || true
run_cpu timeout -k 10 240 python bin/serve_bench.py $KVC \
  --json /tmp/hvd_kvc_dev.json || true
python - <<'PYEOF'
import json
tier = json.loads(open("/tmp/hvd_kvc_tier.json").read().splitlines()[-1])
dev = json.loads(open("/tmp/hvd_kvc_dev.json").read().splitlines()[-1])
# The device-only run must actually be block-starved for the
# comparison to mean anything.
assert dev["rejected_blocks_exhausted"] > 0, dev
assert tier["rejected_blocks_exhausted"] < dev["rejected_blocks_exhausted"], (
    tier["rejected_blocks_exhausted"], dev["rejected_blocks_exhausted"])
assert tier["completed"] > dev["completed"], (
    tier["completed"], dev["completed"])
# The mechanism, not just the outcome: the tier leg preserved its
# chains (hits) where the device-only leg destroyed them (misses)...
assert tier["prefix_hit_rate"] > 0.8, tier["prefix_hit_rate"]
assert dev["prefix_hit_rate"] < 0.5, dev["prefix_hit_rate"]
# ...by round-tripping blocks through the host tier, books balanced.
assert tier["kv_offload_blocks_total"] > 0, tier
assert tier["kv_prefetch_blocks_total"] > 0, tier
for leg in (tier, dev):
    b = leg["blocks"]
    assert b["free"] + b["used"] == b["total"], b
    assert b["host_used"] + b["host_free"] == b["host_total"], b
print(f"device-only: {dev['completed']}/{dev['sent']} completed, "
      f"{dev['rejected_blocks_exhausted']} blocks_exhausted, hit_rate "
      f"{dev['prefix_hit_rate']:.2f}")
print(f"host-tiered: {tier['completed']}/{tier['sent']} completed, "
      f"{tier['rejected_blocks_exhausted']} blocks_exhausted, hit_rate "
      f"{tier['prefix_hit_rate']:.2f}, offload "
      f"{tier['kv_offload_blocks_total']} / prefetch "
      f"{tier['kv_prefetch_blocks_total']}")
print("KV HIERARCHY CAPACITY OK")
PYEOF

echo "== KV hierarchy: new tests stay inside the tier-1 wall budget =="
# The edge-geometry suite rides tier-1 (~430 s of headroom under the
# 870 s cap today); this guard fails the PR that lets it creep toward
# three-digit seconds, and --durations names the offenders.
run_cpu timeout -k 10 120 python -m pytest tests/test_kv_hierarchy.py \
  -q --durations=8 -p no:cacheprovider

echo "== serving fleet: closed-loop autoscaler drill (spike -> grow -> drain -> shrink) =="
# ISSUE 13 acceptance: a traffic spike one replica cannot absorb must
# (a) fire >= 1 grow scale-event and recover queue depth to 0, then
# once traffic stops (b) drain the extra replicas losing ZERO admitted
# streams and shrink back to min replicas — and the fleet's
# completion-order-free stream digest must be IDENTICAL to a
# single-replica run of the same seeded traffic (drain/dispatch may
# move streams between replicas, never change a token).
rm -f /tmp/hvd_fleet_ref.json /tmp/hvd_fleet_auto.json
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 150 --duration 6 --deadline-ms 0 --slots 1 --gen-tokens 32 \
  --max-queue 2000 --json /tmp/hvd_fleet_ref.json
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 150 --duration 6 --deadline-ms 0 --slots 1 --gen-tokens 32 \
  --max-queue 2000 --replicas 3 --autoscale --json /tmp/hvd_fleet_auto.json
python - <<'PYEOF'
import json
auto_lines = [json.loads(l) for l in open("/tmp/hvd_fleet_auto.json")]
row = [l for l in auto_lines if "stream_digest" in l][-1]
fleet = [l for l in auto_lines if l.get("fleet")][-1]
ref = [json.loads(l) for l in open("/tmp/hvd_fleet_ref.json")
       if "stream_digest" in l][-1]
assert row["completed"] == row["sent"], (row["completed"], row["sent"])
assert row["overload_drops"] == 0 and row["failed"] == 0, row
assert fleet["scale_events"]["grow"] >= 1, \
    f"spike never grew the fleet: {fleet['scale_events']}"
assert fleet["queue_depth_final"] == 0, \
    f"queue depth never recovered: {fleet['queue_depth_final']}"
assert fleet["ready_final"] == fleet["min_replicas"] == 1, \
    f"fleet did not shrink back to min: {fleet}"
assert fleet["drained_lost_streams"] == 0, fleet
assert row["stream_digest"] == ref["stream_digest"], \
    "fleet dispatch/drain changed a token stream"
print(f"autoscaler closed loop OK: grow x{fleet['scale_events']['grow']}"
      f" -> depth 0 -> shrink x{fleet['scale_events']['shrink']} to "
      f"{fleet['ready_final']} replica(s), {row['completed']} streams, "
      f"0 lost, digest == single-replica run")
print("FLEET AUTOSCALER OK")
PYEOF

echo "== multi-tenant adapters: per-tenant digest drill (2 LoRA tenants + base, ONE engine) =="
# ISSUE 14 acceptance: 2 adapters + base traffic through one engine —
# a mixed-adapter decode batch is ONE compiled program and every
# tenant's stream must be bit-identical to a single-tenant reference
# run of the same seeded schedule (--adapter-only replays the schedule
# submitting only that tenant). Digests are completion-order-free, so
# batch composition can differ arbitrarily; tokens may not.
rm -f /tmp/hvd_mt_mix.json /tmp/hvd_mt_base.json /tmp/hvd_mt_a0.json /tmp/hvd_mt_a1.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --adapters 2 --json /tmp/hvd_mt_mix.json
for t in base a0 a1; do
  run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
    --qps 20 --duration 5 --deadline-ms 0 --adapters 2 --adapter-only $t \
    --json /tmp/hvd_mt_$t.json
done
python - <<'PYEOF'
import json
mix = [json.loads(l) for l in open("/tmp/hvd_mt_mix.json")][-1]
assert mix["completed"] == mix["sent"] and mix["failed"] == 0, mix
assert mix["adapters_resident"] == 2, mix.get("adapters_resident")
for t in ("base", "a0", "a1"):
    solo = [json.loads(l) for l in open(f"/tmp/hvd_mt_{t}.json")][-1]
    assert solo["completed"] == solo["sent"] and solo["failed"] == 0, solo
    assert solo["tenant_sent"][t] == mix["tenant_sent"][t], \
        f"{t}: schedule replay drifted ({solo['tenant_sent']} vs {mix['tenant_sent']})"
    assert mix["stream_digests"][t] == solo["stream_digests"][t], \
        f"tenant {t}: mixed-batch stream differs from its single-tenant run"
# per-tenant latency split must be populated for every tenant
for t in ("base", "a0", "a1"):
    assert mix["tenants"][t]["generations_total"] == mix["tenant_completed"][t], mix["tenants"]
print("multi-tenant digests OK: base/a0/a1 each bit-identical mixed vs solo "
      f"({mix['completed']} streams mixed)")
PYEOF

echo "== serving chaos drill: replica_kill mid-stream -> deterministic stream failover =="
# ISSUE 15 acceptance: a replica killed mid-stream strands ZERO client
# streams — the router re-dispatches every stranded stream to a
# surviving replica and replays it with the already-emitted prefix
# suppressed, so every client-visible stream is bit-identical to an
# unkilled single-replica run of the same seeded traffic. Pinned for
# greedy adapter-bearing traffic (failover re-retains the LoRA row on
# the destination replica) AND seeded-sampling traffic; the killed
# replica leaves a flight-recorder post-mortem naming its in-flight
# streams. slots=2/gen-tokens=32 keeps streams long enough that the
# least-load dispatch actually spreads traffic onto r1 before the kill.
rm -f /tmp/hvd_fo_aref.json /tmp/hvd_fo_akill.json \
      /tmp/hvd_fo_sref.json /tmp/hvd_fo_skill.json
FR_SERVE="$(mktemp -d)"
export FR_SERVE
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --adapters 1 --adapter-mix 0,1 --json /tmp/hvd_fo_aref.json
HVD_FLIGHTREC_DIR="$FR_SERVE" \
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --adapters 1 --adapter-mix 0,1 --replicas 2 \
  --chaos 'replica_kill=r1@stream=3' --json /tmp/hvd_fo_akill.json
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --temperature 0.7 --json /tmp/hvd_fo_sref.json
HVD_FLIGHTREC_DIR="$FR_SERVE" \
run_cpu timeout -k 10 300 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --temperature 0.7 --replicas 2 \
  --chaos 'replica_kill=r1@stream=3' --json /tmp/hvd_fo_skill.json
python - <<'PYEOF'
import glob, json, os
def rows(path):
    return [json.loads(l) for l in open(path)]
for ref_p, kill_p, label in (
        ("/tmp/hvd_fo_aref.json", "/tmp/hvd_fo_akill.json",
         "greedy+adapter"),
        ("/tmp/hvd_fo_sref.json", "/tmp/hvd_fo_skill.json", "seeded")):
    ref = [r for r in rows(ref_p) if "stream_digest" in r][-1]
    kill_rows = rows(kill_p)
    row = [r for r in kill_rows if "stream_digest" in r][-1]
    fleet = [r for r in kill_rows if r.get("fleet")][-1]
    assert row["completed"] == row["sent"] and row["failed"] == 0, \
        (label, row["completed"], row["sent"], row["failed"])
    assert row["overload_drops"] == 0 and row["deadline_drops"] == 0, \
        (label, row)
    assert fleet["failover"]["resumed"] >= 1, (label, fleet["failover"])
    assert fleet["failover"]["exhausted"] == 0, (label, fleet["failover"])
    assert fleet["stranded"] >= 1, (label, fleet)
    assert fleet["drained_lost_streams"] == 0, (label, fleet)
    # The kill actually landed on r1 (its dispatch history folded into
    # the bounded "retired" series on eviction).
    assert fleet["dispatch"].get("retired", 0) >= 1, (label, fleet)
    assert row["stream_digests"] == ref["stream_digests"], \
        f"{label}: failover changed a client-visible token stream"
    print(f"{label}: {fleet['stranded']} stranded -> "
          f"{fleet['failover']['resumed']} resumed, 0 exhausted, "
          f"digests identical to unkilled single-replica run")
dumps = glob.glob(os.environ["FR_SERVE"] + "/hvd_flightrec.rank*.json")
assert dumps, "killed replica left no flight-recorder post-mortem"
body = open(dumps[0]).read()
assert "serve_crash" in body and "replica_kill" in body, \
    f"post-mortem names neither the crash nor the drill: {body[:200]}"
print("post-mortem OK: dead replica dumped its in-flight streams")
print("SERVING FAILOVER OK")
PYEOF

echo "== out-of-process replicas: SIGKILL a subprocess replica mid-stream -> cross-process failover =="
# ISSUE 16 acceptance: the fault-tolerance plane crossed a real process
# boundary. A 3-replica SUBPROCESS fleet (each member a `python -m
# horovod_tpu.serve.proc_replica` worker behind a ProcReplicaClient)
# takes the same seeded traffic as a thread fleet; the chaos clause
# SIGKILLs r1's worker process mid-stream (a dead pid, not a flipped
# flag). Pinned: zero lost streams, >=1 failover resume, and every
# client-visible stream digest IDENTICAL to the unkilled THREAD-fleet
# reference — bit-identity across both topologies and a real SIGKILL.
# The dead child leaves its serve_crash post-mortem in its PER-REPLICA
# dump dir ($FR_PROC/r1), written before the SIGKILL lands.
rm -f /tmp/hvd_proc_tref.json /tmp/hvd_proc_kill.json
FR_PROC="$(mktemp -d)"
export FR_PROC
run_cpu timeout -k 10 420 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --replicas 3 --json /tmp/hvd_proc_tref.json
HVD_FLIGHTREC_DIR="$FR_PROC" \
run_cpu timeout -k 10 420 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --replicas 3 --replica-procs \
  --chaos 'replica_proc_kill=r1@stream=3' --json /tmp/hvd_proc_kill.json
python - <<'PYEOF'
import glob, json, os
def rows(path):
    return [json.loads(l) for l in open(path)]
ref = [r for r in rows("/tmp/hvd_proc_tref.json")
       if "stream_digest" in r][-1]
kill_rows = rows("/tmp/hvd_proc_kill.json")
row = [r for r in kill_rows if "stream_digest" in r][-1]
fleet = [r for r in kill_rows if r.get("fleet")][-1]
# The topology stamp makes the cross-topology comparison self-checking.
assert ref["topology"] == "thread" and row["topology"] == "process", \
    (ref.get("topology"), row.get("topology"))
assert row["completed"] == row["sent"] and row["failed"] == 0, \
    (row["completed"], row["sent"], row["failed"])
assert row["overload_drops"] == 0 and row["deadline_drops"] == 0, row
assert fleet["failover"]["resumed"] >= 1, fleet["failover"]
assert fleet["failover"]["exhausted"] == 0, fleet["failover"]
assert fleet["stranded"] >= 1, fleet
assert fleet["drained_lost_streams"] == 0, fleet
# The SIGKILL actually landed on a member (its dispatch history folded
# into the bounded "retired" series when the dead pid was evicted).
assert fleet["dispatch"].get("retired", 0) >= 1, fleet
assert row["stream_digests"] == ref["stream_digests"], \
    "process-kill failover changed a client-visible token stream vs " \
    "the thread-fleet reference"
print(f"proc fleet: {fleet['stranded']} stranded -> "
      f"{fleet['failover']['resumed']} resumed, 0 exhausted; digests "
      f"identical to the unkilled thread fleet")
# The dead CHILD's post-mortem: per-replica dump dir, serve_crash event
# naming the in-flight streams, written before the self-SIGKILL.
dumps = glob.glob(os.environ["FR_PROC"] + "/r1/hvd_flightrec.rank*.json")
assert dumps, "SIGKILLed child left no flight-recorder post-mortem"
body = open(dumps[0]).read()
assert "serve_crash" in body and "replica_proc_kill" in body, \
    f"child post-mortem names neither the crash nor the drill: {body[:200]}"
print("post-mortem OK: dead child dumped its in-flight streams before "
      "the SIGKILL")
print("OUT-OF-PROCESS FAILOVER OK")
PYEOF

echo "== speculative decoding: greedy digests spec-on == spec-off, accept rate + tokens/step pinned =="
# ISSUE 17 acceptance: --spec-k 4 drafts with the self-speculative
# n-gram proposer and scores k+1 positions in ONE verify forward.
# Pinned: (a) greedy speculated streams digest-IDENTICAL to the spec-off
# reference on BOTH KV layouts (bit-identity is the contract, not a
# tolerance — and paged greedy digests equal contiguous ones, so one
# reference covers both), (b) spec_accept_rate > 0 (tiny greedy models
# settle into repeating cycles the drafter catches — speculation
# actually fired), (c) effective tokens per decode step > 1.0
# (speculation actually emitted multi-token steps, counting no-draft
# fallback steps against it).
rm -f /tmp/hvd_spec_off.json /tmp/hvd_spec_on.json /tmp/hvd_spec_paged.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --gen-tokens 32 \
  --json /tmp/hvd_spec_off.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --gen-tokens 32 --spec-k 4 \
  --json /tmp/hvd_spec_on.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 20 --duration 5 --deadline-ms 0 --gen-tokens 32 --spec-k 4 \
  --kv-layout paged --block-size 16 --json /tmp/hvd_spec_paged.json
python - <<'PYEOF'
import json
off = [json.loads(l) for l in open("/tmp/hvd_spec_off.json")][-1]
on = [json.loads(l) for l in open("/tmp/hvd_spec_on.json")][-1]
paged = [json.loads(l) for l in open("/tmp/hvd_spec_paged.json")][-1]
assert off["spec_k"] == 0 and off["spec_accept_rate"] is None, off["spec_k"]
for run, label in ((on, "contiguous"), (paged, "paged")):
    assert run["completed"] == run["sent"] and run["failed"] == 0, \
        (label, run["completed"], run["sent"], run["failed"])
    assert run["spec_k"] == 4, (label, run["spec_k"])
    assert run["stream_digest"] == off["stream_digest"], \
        f"{label}: speculation changed a greedy token stream"
    assert run["spec_accept_rate"] and run["spec_accept_rate"] > 0, \
        (label, run["spec_accept_rate"])
    assert run["tokens_per_step"] and run["tokens_per_step"] > 1.0, \
        (label, run["tokens_per_step"])
    print(f"{label}: digest == spec-off reference, accept_rate "
          f"{run['spec_accept_rate']:.3f}, "
          f"{run['tokens_per_step']:.2f} tokens/step")
print("SPECULATIVE DECODING OK")
PYEOF

echo "== speculative decoding chaos: SIGKILL a subprocess replica mid-speculated-stream =="
# ISSUE 17 acceptance (failover half): a speculated stream's failover
# envelope must replay BIT-identically after a real process death. A
# 3-member subprocess fleet speculates (--spec-k rides the child spec);
# the chaos clause SIGKILLs r1 mid-stream. Pinned: zero lost streams,
# >=1 resume, every client-visible stream digest IDENTICAL to the
# spec-off single-engine reference (speculation AND cross-process
# failover, together, changed no token), and the fleet still reports a
# nonzero acceptance rate aggregated from the children's /stats.
rm -f /tmp/hvd_spec_fo_ref.json /tmp/hvd_spec_fo_kill.json
run_cpu timeout -k 10 420 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --json /tmp/hvd_spec_fo_ref.json
run_cpu timeout -k 10 420 python bin/serve_bench.py --mode generate \
  --qps 60 --duration 3 --deadline-ms 0 --slots 2 --gen-tokens 32 \
  --replicas 3 --replica-procs --spec-k 4 \
  --chaos 'replica_proc_kill=r1@stream=3' --json /tmp/hvd_spec_fo_kill.json
python - <<'PYEOF'
import json
ref = [json.loads(l) for l in open("/tmp/hvd_spec_fo_ref.json")
       if "stream_digest" in l][-1]
kill_rows = [json.loads(l) for l in open("/tmp/hvd_spec_fo_kill.json")]
row = [r for r in kill_rows if "stream_digest" in r][-1]
fleet = [r for r in kill_rows if r.get("fleet")][-1]
assert ref["spec_k"] == 0 and row["spec_k"] == 4, \
    (ref["spec_k"], row["spec_k"])
assert row["completed"] == row["sent"] and row["failed"] == 0, \
    (row["completed"], row["sent"], row["failed"])
assert fleet["failover"]["resumed"] >= 1, fleet["failover"]
assert fleet["failover"]["exhausted"] == 0, fleet["failover"]
assert fleet["stranded"] >= 1, fleet
assert fleet["drained_lost_streams"] == 0, fleet
assert fleet["dispatch"].get("retired", 0) >= 1, fleet
assert row["stream_digests"] == ref["stream_digests"], \
    "speculation + process-kill failover changed a client-visible " \
    "token stream vs the spec-off reference"
assert fleet["spec_accept_rate"] and fleet["spec_accept_rate"] > 0, \
    fleet["spec_accept_rate"]
print(f"spec fleet: {fleet['stranded']} stranded -> "
      f"{fleet['failover']['resumed']} resumed, 0 exhausted; digests "
      f"identical to the spec-off unkilled reference; fleet accept_rate "
      f"{fleet['spec_accept_rate']:.3f}")
print("SPECULATIVE FAILOVER OK")
PYEOF

echo "== multi-tenant adapters: hot-evict under traffic (refusal while referenced, zero lost streams) =="
run_cpu timeout -k 10 240 python - <<'PYEOF'
import time
import jax, jax.numpy as jnp
from horovod_tpu import serve
from horovod_tpu.parallel.transformer import TransformerConfig, init_params
from horovod_tpu.parallel.lora import LoraConfig, init_adapter

cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                        dtype=jnp.float32, unembed_dtype=jnp.float32,
                        attn_backend="xla")
params = init_params(jax.random.PRNGKey(0), cfg)
lora = LoraConfig(rank=2)
reg = serve.AdapterRegistry(cfg, lora, capacity=2)
reg.load("a0", init_adapter(jax.random.PRNGKey(1), cfg, lora, b_scale=0.5))
reg.load("a1", init_adapter(jax.random.PRNGKey(2), cfg, lora, b_scale=0.5))
eng = serve.GenerationEngine(
    params, cfg,
    serve.GenerationConfig(max_slots=2, max_len=64,
                           default_max_new_tokens=48), adapters=reg)
ref = eng.generate([5, 4, 3], adapter="a0", timeout=120)   # quiet reference
h = eng.submit([5, 4, 3], adapter="a0", max_new_tokens=48)  # long live stream
# The row reference is taken AT SUBMIT (caller's thread), so the evict
# attempt races nothing: the refcount holds until the stream completes.
try:
    reg.evict("a0")
    raise SystemExit("FAIL: evict succeeded while a live stream references a0")
except RuntimeError as e:
    assert "referenced" in str(e), e
r = h.result(120)
assert r["tokens"] == ref["tokens"], \
    "FAIL: eviction attempt perturbed a live stream"
reg.evict("a0")                         # stream done: refcount 0, allowed
assert "a0" not in reg.resident()
n_compiled = len(eng._compiled)
reg.load("a2", init_adapter(jax.random.PRNGKey(3), cfg, lora, b_scale=0.5))
out = eng.generate([5, 4, 3], adapter="a2", timeout=120)    # row reused
assert out["n_tokens"] > 0 and len(eng._compiled) == n_compiled, \
    "FAIL: hot load recompiled"
eng.shutdown()
print("hot-evict drill OK: refusal while referenced, stream finished "
      f"bit-identical ({r['n_tokens']} tokens), row reused with no recompile")
PYEOF

echo "== SLO fairness: starvation drill (chatty tenant saturates, quiet tenant's TTFT holds) =="
# ISSUE 19 acceptance: equal weights {chatty:1, quiet:1}, chatty (base)
# at ~59x the quiet tenant's arrival rate, 300 qps against 2 decode
# slots — the chatty backlog is hundreds deep by design. Under FIFO
# the quiet tenant's TTFT is that backlog's drain time (minutes);
# under WDRR it is its own near-empty line. Pinned: every quiet
# stream completes, its p50 TTFT holds a 10 s SLO, and the chatty
# tenant is throttled — NOT failed (deadline 0, huge queue: zero
# drops, zero failures for either tenant).
rm -f /tmp/hvd_fair.json
run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
  --qps 300 --duration 2 --deadline-ms 0 --slots 2 --gen-tokens 8 \
  --max-queue 4096 --adapters 1 --adapter-mix 59,1 \
  --tenant-weights base:1,a0:1 --tenant-slo-ms a0:10000 \
  --json /tmp/hvd_fair.json
python - <<'PYEOF'
import json
row = [json.loads(l) for l in open("/tmp/hvd_fair.json")][-1]
assert row["failed"] == 0 and row["overload_drops"] == 0, row
sent, done = row["tenant_sent"], row["tenant_completed"]
assert sent["base"] > 10 * sent["a0"] > 0, \
    f"traffic shape degenerate, drill proves nothing: {sent}"
assert done["a0"] == sent["a0"], \
    f"quiet tenant starved: {done['a0']}/{sent['a0']} completed"
assert done["base"] == sent["base"], \
    f"chatty tenant was FAILED, not throttled: {done['base']}/{sent['base']}"
p50 = row["tenant_ttft_ms"]["a0"]["p50"]
assert p50 <= row["tenant_slo_ms"]["a0"], \
    f"quiet tenant p50 TTFT {p50:.0f} ms blew its " \
    f"{row['tenant_slo_ms']['a0']:.0f} ms SLO behind the chatty backlog"
assert row["tenants"]["a0"]["slo_ttft_target_ms"] == 10000.0, row["tenants"]
print(f"fairness OK: quiet {done['a0']}/{sent['a0']} complete, "
      f"p50 TTFT {p50:.0f} ms <= 10000 ms SLO while chatty sent "
      f"{sent['base']} ({done['base']} complete, 0 failed)")
print("STARVATION DRILL OK")
PYEOF

echo "== SLO preemption: priority evictions stay digest-pinned (slots=1, mixed classes) =="
# ISSUE 19 acceptance: a0 in priority class 1 over ONE decode slot —
# every a0 arrival evicts the running base stream, which later resumes
# with its emitted prefix replayed suppressed-and-verified. Pinned:
# preemptions actually happened, none exhausted (the drill raises the
# retry budget so an unlucky eviction streak can't flake the run), and
# BOTH tenants' digests are bit-identical to their single-tenant
# replays of the same seeded schedule — eviction is invisible in the
# streams, visible only in the counters.
rm -f /tmp/hvd_pre_mix.json /tmp/hvd_pre_base.json /tmp/hvd_pre_a0.json
for only in "" base a0; do
  out=mix; flags=""
  if [ -n "$only" ]; then out=$only; flags="--adapter-only $only"; fi
  run_cpu timeout -k 10 240 python bin/serve_bench.py --mode generate \
    --qps 100 --duration 3 --deadline-ms 0 --slots 1 --gen-tokens 16 \
    --max-queue 4096 --adapters 1 --adapter-mix 4,1 \
    --priority-mix a0:1 --preempt-retries 1000 $flags \
    --json /tmp/hvd_pre_$out.json
done
python - <<'PYEOF'
import json
mix = [json.loads(l) for l in open("/tmp/hvd_pre_mix.json")][-1]
assert mix["completed"] == mix["sent"] and mix["failed"] == 0, mix
assert mix["preemptions"] >= 1, \
    f"priority class 1 over one slot never evicted: {mix['preemptions']}"
assert mix["preempt_exhausted"] == 0, mix
for t in ("base", "a0"):
    solo = [json.loads(l) for l in open(f"/tmp/hvd_pre_{t}.json")][-1]
    assert solo["completed"] == solo["sent"] and solo["failed"] == 0, solo
    assert solo["tenant_sent"][t] == mix["tenant_sent"][t], \
        f"{t}: schedule replay drifted"
    assert mix["stream_digests"][t] == solo["stream_digests"][t], \
        f"tenant {t}: preemption changed a client-visible token stream"
print(f"preemption OK: {mix['preemptions']} evictions, "
      f"{mix['preempt_resumed']} resumed, 0 exhausted; base and a0 "
      f"digests identical to their uninterrupted solo runs")
print("PREEMPTION DIGEST OK")
PYEOF

echo "== SLO budgets: per-tenant blocks_exhausted rejects ONE tenant, neighbors admit =="
run_cpu timeout -k 10 240 python - <<'PYEOF'
import jax, jax.numpy as jnp
from horovod_tpu import serve
from horovod_tpu.exceptions import ServerOverloadedError
from horovod_tpu.parallel.transformer import TransformerConfig, init_params
from horovod_tpu.parallel.lora import LoraConfig, init_adapter

cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                        dtype=jnp.float32, unembed_dtype=jnp.float32,
                        attn_backend="xla")
params = init_params(jax.random.PRNGKey(0), cfg)
lora = LoraConfig(rank=2)
reg = serve.AdapterRegistry(cfg, lora, capacity=2)
reg.load("a0", init_adapter(jax.random.PRNGKey(1), cfg, lora, b_scale=0.5))
reg.load("a1", init_adapter(jax.random.PRNGKey(2), cfg, lora, b_scale=0.5))
eng = serve.GenerationEngine(
    params, cfg,
    serve.GenerationConfig(max_slots=4, max_len=64,
                           default_max_new_tokens=16, kv_layout="paged",
                           block_size=16,
                           tenant_block_budgets={"a0": 2}), adapters=reg)
# a0's worst case: blocks_for(3 + 16 - 1) = 2 == its whole budget, so a
# SECOND in-flight a0 stream must be rejected at the door — blocks
# exhausted for a0 ALONE, with a usable backoff hint...
h0 = eng.submit([5, 4, 3], adapter="a0")
try:
    eng.submit([6, 5, 4], adapter="a0")
    raise SystemExit("FAIL: second a0 stream fit in a 2-block budget")
except ServerOverloadedError as e:
    assert "blocks_exhausted" in str(e), e
    assert 50.0 <= e.retry_after_ms <= 30000.0, e.retry_after_ms
# ...while the neighbors' doors never move: base and a1 admit at the
# same instant a0 is budget-starved (the isolation half).
hb = eng.submit([6, 5, 4])
h1 = eng.submit([6, 5, 4], adapter="a1")
for h in (h0, hb, h1):
    assert h.result(120)["n_tokens"] == 16
assert eng.stats()["rejected_blocks_exhausted"] >= 1
assert eng.stats()["blocks_by_tenant"]["budgets"] == {"a0": 2}
# Drained: the ledger released a0's headroom and it admits again.
r = eng.generate([5, 4, 3], adapter="a0", timeout=120)
assert r["n_tokens"] == 16
eng.shutdown()
print("budget isolation OK: a0 rejected blocks_exhausted (retry hint "
      "attached) while base and a1 admitted; headroom returned on drain")
print("BUDGET ISOLATION OK")
PYEOF

echo "== striped host reduce (multi-core validation, gated on nproc) =="
if [ "$(nproc)" -gt 1 ]; then
  # On a >=4-core host, striping must not LOSE to the serial reduce at
  # coordinator scale (docs/coordination.md "Star-plane throughput under
  # load"); on 2-3 cores the script measures and reports (median of
  # rounds) without asserting — the 4-way stripe needs 4 cores for the
  # claim to even apply, and loaded 2-core CI runners were flaking the
  # bound without any product change.
  python tests/striping_bench.py
else
  echo "skip: single-core host — striping is neutral by construction here"
  echo "      (correctness is covered by tests/test_coord.py; the"
  echo "       multi-core perf claim is marked unmeasured in"
  echo "       docs/coordination.md until CI lands on a multi-core host)"
fi

echo "== container image (gated on docker availability) =="
if command -v docker >/dev/null 2>&1; then
  docker build -t horovod-tpu-ci .
  docker run --rm horovod-tpu-ci \
    python -m horovod_tpu.launcher -np 2 --cpu python tests/launcher_worker.py
else
  echo "skip: no docker daemon in this environment — the Dockerfile builds"
  echo "      from the baked-in wheels only; multi-host wiring is"
  echo "      documented in docs/running.md"
fi

echo "== tpurun launcher smoke (2 ranks, env-world) =="
python -m horovod_tpu.launcher -np 2 --cpu python tests/launcher_worker.py

# Flight-recorder hygiene for every chaos leg below: dumps default to
# the cwd, so a previous run's hvd_flightrec.rank*.json in the repo root
# could satisfy a pinned grep/assert from THIS run's leg (and stale
# dumps mask real post-mortems). Clean them, then point the default dump
# dir at a tmp dir — legs that pin dump CONTENTS still set their own
# HVD_FLIGHTREC_DIR inline, which overrides the export.
rm -f hvd_flightrec.rank*.json
HVD_FLIGHTREC_DIR="$(mktemp -d)"
export HVD_FLIGHTREC_DIR

echo "== fault-injection smoke: kill rank 2 at step 3, recover via --restarts 1 =="
# The anti-hang drill (docs/fault_tolerance.md): rank 2 is SIGKILLed mid
# -training; the coordinator must ABORT the world (WorkerFailureError, no
# hang), tpurun must relaunch it once, and run_with_recovery must resume
# from the last committed step and finish. The hard `timeout` is the
# assertion — a regression that reintroduces the reference's dead-rank
# hang fails CI here instead of wedging it.
FT_DIR=$(mktemp -d)
HVD_FAULT_SPEC=rank=2:kill@step=3 HVD_ELASTIC_DIR="$FT_DIR" \
HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu --restarts 1 \
  python tests/elastic_worker.py
# And without --restarts the same drill must FAIL FAST (nonzero AND not
# a timeout kill): exit 124/137 would mean the job HUNG until `timeout`
# shot it — the exact regression this leg exists to catch.
FT_DIR2=$(mktemp -d)
set +e
HVD_FAULT_SPEC=rank=2:kill@step=3 HVD_ELASTIC_DIR="$FT_DIR2" \
HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=6 \
  timeout -k 10 180 \
  python -m horovod_tpu.launcher -np 4 --cpu \
  python tests/elastic_worker.py
ft_rc=$?
set -e
if [ "$ft_rc" -eq 0 ]; then
  echo "FAIL: killed-rank world exited 0 without restarts" >&2
  exit 1
elif [ "$ft_rc" -eq 124 ] || [ "$ft_rc" -eq 137 ]; then
  echo "FAIL: killed-rank world HUNG until timeout killed it (rc=$ft_rc)" >&2
  exit 1
fi
rm -rf "$FT_DIR" "$FT_DIR2"

echo "== chaos leg: post-commit checkpoint truncation -> verified fallback restore =="
# ISSUE 4 acceptance (a): ckpt:truncate@step=3 tears the step-3 checkpoint
# strictly AFTER its two-phase commit (marker on disk), then rank 2 is
# killed — the restarted world must DISCARD the torn-but-committed step
# via the integrity-manifest walk, resume from verified step 2, and still
# finish bit-identical to an uninterrupted run. A regression that trusts
# the marker without verifying bytes restores garbage and diverges here.
CH_REF=$(mktemp -d); CH_DIR=$(mktemp -d)
HVD_ELASTIC_DIR="$CH_REF" HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu \
  python tests/elastic_worker.py 2>&1 | tee /tmp/chaos_ref.out
HVD_FAULT_SPEC=ckpt:truncate@step=3,rank=2:kill@step=3 \
HVD_ELASTIC_DIR="$CH_DIR" HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu --restarts 1 \
  python tests/elastic_worker.py 2>&1 | tee /tmp/chaos_run.out
grep -q "resuming from verified step 2" /tmp/chaos_run.out || {
  echo "FAIL: fallback walk never fired — the torn commit was trusted" >&2
  exit 1
}
REF_SUM=$(grep -o "FINAL [0-9.]*" /tmp/chaos_ref.out | sort -u)
CH_SUM=$(grep -o "FINAL [0-9.]*" /tmp/chaos_run.out | sort -u)
if [ -z "$REF_SUM" ] || [ "$REF_SUM" != "$CH_SUM" ]; then
  echo "FAIL: post-recovery params diverge from uninterrupted run" >&2
  echo "  reference: $REF_SUM" >&2
  echo "  chaos:     $CH_SUM" >&2
  exit 1
fi
rm -rf "$CH_REF" "$CH_DIR"

echo "== chaos leg: NaN-injection -> bit-exact skip-step, HLO all-reduce count pinned =="
# ISSUE 4 acceptance (b)+(c): one non-finite microbatch leaves params
# BIT-identical (the in-jit guard gates the update), flags bad_step=1,
# the next finite batch trains normally, and arming the guard adds ZERO
# all-reduces to the lowered step.
run_cpu timeout -k 10 300 python - <<'EOF'
import re
import flax.linen as nn
import jax, jax.numpy as jnp, numpy as np, optax
import horovod_tpu as hvd
from horovod_tpu import training

class M(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

hvd.init()
model = M()
state, opt = training.create_train_state(
    model, jax.random.PRNGKey(0), jnp.zeros((2, 8)), optax.adam(1e-3))
step = training.make_train_step(model, opt, guard_nonfinite=True,
                                donate=False)
rng = np.random.RandomState(0)
x = rng.randn(16, 8).astype(np.float32)
y = rng.randint(0, 10, (16,))
x[3] = np.nan
before = jax.tree_util.tree_map(np.asarray, state.params)
s2, m = step(state, (x, y))
assert float(m["bad_step"]) == 1.0, m
for a, b in zip(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, s2.params)),
        jax.tree_util.tree_leaves(before)):
    np.testing.assert_array_equal(a, b)
x2 = rng.randn(16, 8).astype(np.float32)
s3, m2 = step(s2, (x2, y))
assert float(m2["bad_step"]) == 0.0, m2
n_guard = len(re.findall(r"\ball_reduce\b",
                         step.lower(s2, (x2, y)).as_text()))
bare = training.make_train_step(model, opt, guard_nonfinite=False,
                                donate=False)
n_bare = len(re.findall(r"\ball_reduce\b",
                        bare.lower(s2, (x2, y)).as_text()))
assert n_guard == n_bare, (n_guard, n_bare)
print(f"NaN smoke OK: skip-step bit-exact, all_reduce count {n_guard} "
      f"unchanged by guard")
EOF

echo "== telemetry leg: /metrics exposition on the generation engine (ISSUE 12) =="
# curl the serving /metrics route during a generation smoke and pin the
# NAMED series the fleet tooling keys on (docs/observability.md):
# the TTFT histogram buckets and the paged KV block-pool gauges.
run_cpu timeout -k 10 240 python - <<'EOF'
import subprocess, urllib.request
import jax, jax.numpy as jnp
from horovod_tpu import serve
from horovod_tpu.obs.registry import parse_exposition
from horovod_tpu.parallel.transformer import TransformerConfig, init_params

cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=2,
                        d_ff=32, dtype=jnp.float32,
                        unembed_dtype=jnp.float32, attn_backend="xla")
params = init_params(jax.random.PRNGKey(0), cfg)
eng = serve.GenerationEngine(params, cfg, serve.GenerationConfig(
    max_slots=2, max_len=16, default_max_new_tokens=4,
    kv_layout="paged", block_size=4))
eng.warmup()
for _ in range(3):
    eng.generate([3, 1, 4, 1, 5], timeout=60)
with serve.HttpServer(generate=eng) as srv:
    url = f"http://{srv.host}:{srv.port}/metrics"
    try:
        body = subprocess.run(["curl", "-sf", url], check=True,
                              capture_output=True).stdout.decode()
    except (FileNotFoundError, subprocess.CalledProcessError):
        body = urllib.request.urlopen(url).read().decode()
parsed = parse_exposition(body)
names = {k[0] for k in parsed}
for want in ("hvd_generate_ttft_seconds_bucket", "hvd_kv_blocks_free",
             "hvd_kv_blocks_total", "hvd_tokens_generated_total",
             "hvd_requests_total", "hvd_uptime_seconds"):
    assert want in names, f"missing series {want}: {sorted(names)}"
assert parsed[("hvd_tokens_generated_total",
               (("engine", "generate"),))] >= 3
assert body.count("# TYPE hvd_generations_total counter") == 1
eng.shutdown()
print(f"GENERATION /metrics OK: {len(parsed)} series, valid exposition")
EOF

echo "== telemetry leg: scrape 2 live training ranks + tpurun --metrics-summary =="
# A 2-rank env-world Trainer job with HVD_METRICS_PORT set: both rank
# listeners (base+0, base+1) must serve exposition text WHILE the job
# trains, and the one-shot fleet poller must aggregate them into one
# "2/2 ranks up" line — the PR-9 supervisor's first real fleet view.
rm -f /tmp/rank0_metrics.txt /tmp/rank1_metrics.txt /tmp/fleet_line.out
TL_PORT=$(python -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')
HVD_METRICS_PORT=$TL_PORT HVD_METRICS_HOST=127.0.0.1 \
HVD_STEP_SLEEP_MS=300 HVD_TOTAL_STEPS=60 \
  timeout -k 10 180 \
  python -m horovod_tpu.launcher -np 2 --cpu \
  python tests/obs_worker.py > /tmp/telemetry_train.out 2>&1 &
TL_PID=$!
trap 'kill "$TL_PID" 2>/dev/null || true' EXIT
# NB: scrape to files, grep the files — `curl | grep -q` under pipefail
# flakes on grep's early-exit SIGPIPE back into curl.
tl_ok=""
for _ in $(seq 1 120); do
  curl -sf "http://127.0.0.1:$TL_PORT/metrics" \
    -o /tmp/rank0_metrics.txt 2>/dev/null || true
  curl -sf "http://127.0.0.1:$((TL_PORT+1))/metrics" \
    -o /tmp/rank1_metrics.txt 2>/dev/null || true
  # Nonzero step counts: the counters REGISTER at Trainer construction,
  # so a zero-valued match would race the first actual step (and the
  # first exchange, which registers the collective counters).
  if grep -Eq 'hvd_steps_total\{rank="0"\} [1-9]' /tmp/rank0_metrics.txt \
       2>/dev/null \
     && grep -Eq 'hvd_steps_total\{rank="1"\} [1-9]' \
       /tmp/rank1_metrics.txt 2>/dev/null; then
    tl_ok=1; break
  fi
  sleep 0.5
done
[ -n "$tl_ok" ] || {
  echo "FAIL: training ranks never served /metrics" >&2
  cat /tmp/telemetry_train.out >&2
  exit 1
}
for series in hvd_step_seconds_bucket hvd_samples_total \
              hvd_collective_submits_total hvd_world_size; do
  grep -q "$series" /tmp/rank0_metrics.txt || {
    echo "FAIL: rank 0 /metrics missing series $series" >&2
    exit 1
  }
done
python -m horovod_tpu.launcher -np 2 --metrics-summary \
  --metrics-port "$TL_PORT" | tee /tmp/fleet_line.out
grep -q "fleet: 2/2 ranks up" /tmp/fleet_line.out || {
  echo "FAIL: --metrics-summary did not aggregate both ranks" >&2
  exit 1
}
wait "$TL_PID" || {
  echo "FAIL: telemetry training job exited nonzero" >&2
  cat /tmp/telemetry_train.out >&2
  exit 1
}
trap - EXIT
echo "TRAINING /metrics + fleet summary OK"

echo "== telemetry leg: rank kill leaves a flight-recorder post-mortem =="
# rank=1:kill@step=3 SIGKILLs rank 1 mid-training. The drilled rank's
# dump (written by the fault injector, standing in for the platform's
# SIGTERM-before-SIGKILL notice) must name its final completed step;
# the SURVIVOR's dump (triggered by the WorkerFailureError abort) must
# name the dead rank — post-mortems from files, not stdout greps.
FR_DIR=$(mktemp -d)
set +e
HVD_FAULT_SPEC=rank=1:kill@step=3 HVD_FLIGHTREC_DIR="$FR_DIR" \
HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=8 \
  timeout -k 10 180 \
  python -m horovod_tpu.launcher -np 2 --cpu \
  python tests/obs_worker.py > /tmp/telemetry_kill.out 2>&1
fr_rc=$?
set -e
if [ "$fr_rc" -eq 0 ] || [ "$fr_rc" -eq 124 ]; then
  echo "FAIL: kill drill rc=$fr_rc (0 = fault never fired, 124 = hang)" >&2
  cat /tmp/telemetry_kill.out >&2
  exit 1
fi
python - "$FR_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
dead = json.load(open(f"{d}/hvd_flightrec.rank1.json"))
assert dead["last_step"] == 3, dead["last_step"]
assert "kill" in dead["reason"], dead["reason"]
assert any(e["kind"] == "step" and e["step"] == 3
           for e in dead["events"]), dead["events"][-5:]
survivor = json.load(open(f"{d}/hvd_flightrec.rank0.json"))
assert "rank 1" in survivor["reason"], survivor["reason"]
print(f"FLIGHT RECORDER OK: dead rank's last step "
      f"{dead['last_step']}, survivor names the dead rank")
EOF
rm -rf "$FR_DIR"

echo "== live-resize chaos leg: shrink 4 -> 2 in place (quiesce, recommit, re-shard — no restart) =="
# ISSUE 9 acceptance: resize:shrink=2@step=3 must quiesce at a step
# boundary, recommit through the two-phase elastic commit, re-shard in
# place and resume — the log pins quiesce -> recommit -> re-shard and must
# contain NO relaunch line (resize is not a restart), and the final
# checksum must match an uninterrupted 2-rank run bit-for-bit (the
# worker's gradient sums are exact dyadic rationals, invariant to how the
# world splits them).
RS_REF=$(mktemp -d); RS_DIR=$(mktemp -d)
HVD_ELASTIC_DIR="$RS_REF" HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 2 --cpu \
  python tests/resize_worker.py 2>&1 | tee /tmp/resize_ref.out
HVD_FAULT_SPEC=resize:shrink=2@step=3 HVD_ELASTIC_DIR="$RS_DIR" \
HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu --restarts 1 \
  python tests/resize_worker.py 2>&1 | tee /tmp/resize_run.out
# The no-restart pin is the WORKERS' "resuming ... without restart" line:
# it is printed by every surviving rank the instant the in-place re-shard
# completes. (tpurun also prints "resize is not a restart" once its
# commit-confirmation probe lands, but a drill this short can finish
# inside the probe window — the worker line is the deterministic truth.)
for want in "resize: quiesced at step" \
            "recommitting and canonicalizing" \
            "re-sharded optimizer state in place onto world 2" \
            "without restart"; do
  grep -q "$want" /tmp/resize_run.out || {
    echo "FAIL: resize log missing \"$want\" — the quiesce protocol did" \
         "not run" >&2
    exit 1
  }
done
if grep -q "relaunching" /tmp/resize_run.out; then
  echo "FAIL: the shrink took the RESTART path — live resize must keep" \
       "surviving ranks' processes" >&2
  exit 1
fi
RS_REF_SUM=$(grep -o "FINAL [0-9.]*" /tmp/resize_ref.out | sort -u || true)
RS_RUN_SUM=$(grep -o "FINAL [0-9.]*" /tmp/resize_run.out | sort -u || true)
if [ -z "$RS_REF_SUM" ] || [ "$RS_REF_SUM" != "$RS_RUN_SUM" ]; then
  echo "FAIL: live-shrunk run diverges from uninterrupted 2-rank run" >&2
  echo "  reference: $RS_REF_SUM" >&2
  echo "  resized:   $RS_RUN_SUM" >&2
  exit 1
fi
rm -rf "$RS_REF" "$RS_DIR"

echo "== live-resize chaos leg: grow 2 -> 4 under --restarts 0 (resize is not a restart) =="
# The grow leg runs with ZERO restarts budget: if the resize were secretly
# a relaunch, the launch would fail — finishing at world 4 with the
# uninterrupted 4-rank checksum proves the joiners were spawned into the
# LIVE world (state over the wire via elastic.resize_join, no disk).
RG_REF=$(mktemp -d); RG_DIR=$(mktemp -d)
HVD_ELASTIC_DIR="$RG_REF" HVD_TOTAL_STEPS=8 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu \
  python tests/resize_worker.py 2>&1 | tee /tmp/resize_grow_ref.out
HVD_FAULT_SPEC=resize:grow=2@step=3 HVD_ELASTIC_DIR="$RG_DIR" \
HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=8 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 2 --cpu --restarts 0 --max-np 4 \
  python tests/resize_worker.py 2>&1 | tee /tmp/resize_grow.out
grep -q "joining world 4" /tmp/resize_grow.out || {
  echo "FAIL: no rank joined the grown world over the wire" >&2
  exit 1
}
RG_N=$(grep -c "FINAL" /tmp/resize_grow.out || true)
if [ "$RG_N" -ne 4 ]; then
  echo "FAIL: expected 4 FINAL lines after the grow, got $RG_N" >&2
  exit 1
fi
RG_REF_SUM=$(grep -o "FINAL [0-9.]*" /tmp/resize_grow_ref.out | sort -u || true)
RG_RUN_SUM=$(grep -o "FINAL [0-9.]*" /tmp/resize_grow.out | sort -u || true)
if [ -z "$RG_REF_SUM" ] || [ "$RG_REF_SUM" != "$RG_RUN_SUM" ]; then
  echo "FAIL: live-grown run diverges from uninterrupted 4-rank run" >&2
  echo "  reference: $RG_REF_SUM" >&2
  echo "  resized:   $RG_RUN_SUM" >&2
  exit 1
fi
rm -rf "$RG_REF" "$RG_DIR"

echo "== live-resize chaos leg: resize racing a kill -> verified-restore fallback =="
# A rank SIGKILLed while a resize is in flight: the in-place path must be
# ABANDONED and the world fail over to the supervised restart, resuming
# from the quiesce recommit via the verified restore walk.
RK_DIR=$(mktemp -d)
HVD_FAULT_SPEC=resize:shrink=2@step=3,rank=1:kill@step=4 \
HVD_ELASTIC_DIR="$RK_DIR" HVD_HEARTBEAT_TIMEOUT=10 HVD_TOTAL_STEPS=6 \
  timeout -k 10 300 \
  python -m horovod_tpu.launcher -np 4 --cpu --restarts 1 \
  python tests/resize_worker.py 2>&1 | tee /tmp/resize_race.out
# (No grep on tpurun's "ABANDONED" line: whether the supervisor had even
# adopted the pending resize when the kill lands is timing-dependent —
# the invariant is the recovery itself, pinned below.)
grep -q "recovery: resumed from committed step" /tmp/resize_race.out || {
  echo "FAIL: the killed resize never fell back to the verified restore" \
       "walk" >&2
  exit 1
}
RK_SUM=$(grep -o "FINAL [0-9.]*" /tmp/resize_race.out | sort -u || true)
if [ "$(echo "$RK_SUM" | wc -l)" -ne 1 ] || [ -z "$RK_SUM" ]; then
  echo "FAIL: ranks disagree on final params after the raced resize" >&2
  exit 1
fi
rm -rf "$RK_DIR"

echo "== tpurun multi-node smoke (2 simulated hosts x 2 ranks, shared coordinator) =="
# The mpirun -H host1:2,host2:2 analog (docs/running.md): two launcher
# invocations on localhost forming one world of 4 over the coordinator.
MN_PORT=$(python -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')
python -m horovod_tpu.launcher -np 2 --cpu --nnodes 2 --node-rank 0 \
  --coordinator 127.0.0.1:"$MN_PORT" python tests/launcher_worker.py &
MN_PID=$!
# If node 1 fails, set -e exits this script — kill the backgrounded node 0
# too or its ranks sit blocked on collectives holding the stdout pipe open.
trap 'kill "$MN_PID" 2>/dev/null || true' EXIT
python -m horovod_tpu.launcher -np 2 --cpu --nnodes 2 --node-rank 1 \
  --coordinator 127.0.0.1:"$MN_PORT" python tests/launcher_worker.py
wait "$MN_PID"
trap - EXIT

echo "== zero smoke: ZeRO-1 vs replicated parity + world-resize restore =="
# ISSUE 5 acceptance: K steps with zero=True must match the replicated
# optimizer's params to dtype tolerance, the lowered step must contain
# one reduce-scatter + one all-gather per fusion bucket and ZERO
# full-tree all-reduces, and a ZeRO checkpoint committed at world 8 must
# verify and RESUME at world 4 (re-sharded canonical restore,
# docs/checkpointing.md).
run_cpu timeout -k 10 300 python - <<'EOF'
import re, tempfile
import flax.linen as nn
import jax, jax.numpy as jnp, numpy as np, optax
import horovod_tpu as hvd
from horovod_tpu import elastic, training
from horovod_tpu.parallel import checkpoint as ckpt
from horovod_tpu.optimizer import zero_to_canonical

class M(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

def build(zero):
    state, opt = training.create_train_state(
        M(), jax.random.PRNGKey(0), jnp.zeros((2, 8)), optax.adam(1e-2),
        zero=zero)
    return state, training.make_train_step(M(), opt, donate=False)

hvd.init()
rng = np.random.RandomState(0)
rs, rstep = build(False)
zs, zstep = build(True)
for i in range(3):
    b = (rng.randn(16, 8).astype(np.float32), rng.randint(0, 10, (16,)))
    rs, _ = rstep(rs, b)
    zs, zm = zstep(zs, b)
for a, b2 in zip(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, zs.params)),
        jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, rs.params))):
    np.testing.assert_allclose(a, b2, rtol=2e-5, atol=1e-6)
txt = zstep.lower(zs, b).as_text()
nb = len(zs.opt_state.plan.buckets)
counts = (len(re.findall(r"\breduce_scatter\b", txt)),
          len(re.findall(r"\ball_gather\b", txt)),
          len(re.findall(r"\ball_reduce\b", txt)))
assert counts == (nb, nb, 1), (counts, nb)  # the 1 is the loss pmean

d = tempfile.mkdtemp()
es = elastic.ElasticState(zs.params, zs.opt_state, step=3, directory=d,
                          commit_every=1)
path = es.commit()
assert ckpt.verify_checkpoint(path) is True
canon = jax.tree_util.tree_map(
    np.asarray, zero_to_canonical(zs.opt_state).inner)

devs = jax.devices()
hvd.shutdown(); hvd.init(devices=devs[:4])
assert hvd.size() == 4
s4, opt4 = training.create_train_state(
    M(), jax.random.PRNGKey(9), jnp.zeros((2, 8)), optax.adam(1e-2),
    zero=True)
es2 = elastic.ElasticState(s4.params, s4.opt_state, directory=d)
es2.restore()
assert es2.step == 3, es2.step
for a, b2 in zip(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        np.asarray, zero_to_canonical(es2.opt_state).inner)),
        jax.tree_util.tree_leaves(canon)):
    np.testing.assert_array_equal(a, b2)
st = training.TrainState(step=jnp.asarray(3, jnp.int32),
                         params=es2.params, opt_state=es2.opt_state,
                         batch_stats=None)
st2, m = training.make_train_step(M(), opt4, donate=False)(
    st, (rng.randn(16, 8).astype(np.float32), rng.randint(0, 10, (16,))))
assert np.isfinite(float(m["loss"])) and int(st2.step) == 4
print(f"zero smoke OK: parity over 3 steps, HLO rs/ag/ar={counts} for "
      f"{nb} bucket(s), world 8 -> 4 restore bit-exact and resumed")
EOF

echo "== overlap smoke: overlapped bf16-wire parity + HLO count/dtype pins (ISSUE 6) =="
# ISSUE 6 acceptance: 3 steps with overlap=1 wire_dtype=bf16 must match the
# non-overlapped fp32 run within wire tolerance on BOTH the fused-allreduce
# and ZeRO planes, the bucket-collective count must be UNCHANGED by overlap
# (it reorders, never adds), the emission must be barrier-chained in
# backward-completion order, and the wire cast must be visible in HLO
# (bf16 collective operands) without changing any count.
run_cpu timeout -k 10 300 env HVD_OVERLAP=1 HVD_WIRE_DTYPE=bf16 python - <<'EOF'
import os, re
import flax.linen as nn
import jax, jax.numpy as jnp, numpy as np, optax
import horovod_tpu as hvd
from horovod_tpu import training

class M(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        h = x
        for _ in range(3):
            h = nn.relu(nn.Dense(64)(h))
        return nn.Dense(10)(h)

def build(zero, wire, overlap):
    state, opt = training.create_train_state(
        M(), jax.random.PRNGKey(0), jnp.zeros((2, 8)), optax.adam(1e-2),
        zero=zero, wire_dtype=wire, overlap=overlap, fusion_threshold=8000)
    return state, training.make_train_step(M(), opt, donate=False,
                                           overlap=overlap)

hvd.init()
assert os.environ["HVD_OVERLAP"] == "1"  # env defaults are what ship
rng = np.random.RandomState(0)
batches = [(rng.randn(16, 8).astype(np.float32), rng.randint(0, 10, (16,)))
           for _ in range(3)]
for zero in (False, True):
    # The reference pins wire_dtype="fp32" EXPLICITLY: with HVD_WIRE_DTYPE
    # exported above, a None would resolve the env default and the
    # "fp32 run" would silently ride bf16 too.
    rs, rstep = build(zero, "fp32", False)
    ws, wstep = build(zero, "bf16", True)
    for b in batches:
        rs, rm = rstep(rs, b)
        ws, wm = wstep(ws, b)
        np.testing.assert_allclose(float(wm["loss"]), float(rm["loss"]),
                                   rtol=5e-3)
    for a, b2 in zip(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, ws.params)),
            jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, rs.params))):
        np.testing.assert_allclose(a, b2, rtol=5e-2, atol=4e-2)
    # Count pin: overlap reorders, never adds — same collective counts as
    # the non-overlapped plan at the same threshold, wire on or off.
    b = batches[0]
    plain = rstep.lower(rs, b).as_text()
    over = wstep.lower(ws, b).as_text()
    for pat in (r"\ball_reduce\b", r"\breduce_scatter\b", r"\ball_gather\b"):
        n_p, n_o = len(re.findall(pat, plain)), len(re.findall(pat, over))
        assert n_p == n_o, (pat, n_p, n_o)
    if zero:
        nb = len(ws.opt_state.plan.buckets)
        assert len(re.findall(r"\breduce_scatter\b", over)) == nb
        # Wire pin: every scatter operand rides bf16; the update gather
        # stays f32 (replicas end bit-identical).
        scatters = re.findall(
            r"stablehlo\.reduce_scatter(?:[^\n]*\n)+?\s*\}\) : \(tensor<([^>]+)>",
            over)
        assert scatters and all(t.endswith("xbf16") for t in scatters), scatters
    else:
        assert len(re.findall(r"optimization_barrier", over)) >= 1
        assert "xbf16" in over  # cast-on-send reached the lowered module
print("overlap smoke OK: bf16-wire overlap matches fp32 within tolerance "
      "on both modes, collective counts unchanged, wire dtype pinned")
EOF

echo "== overlap smoke: env-world plane (tpurun, coordinator bf16 wire) =="
timeout -k 10 300 python -m horovod_tpu.launcher -np 2 --cpu \
  python tests/overlap_worker.py

echo "== hybrid smoke: dp×tp ZeRO parity vs 1-D + mesh-reshape restore (ISSUE 8) =="
# ISSUE 8 acceptance: a 3-step (dp=2,tp=2) hybrid run with --zero
# --overlap --wire-dtype bf16 must match the 1-D dp=4 fp32 reference on
# the same global batch within the documented wire tolerance, and a
# (dp=2,tp=2) ZeRO checkpoint must restore-and-resume at (dp=4,tp=2)
# through the unchanged elastic commit (the 2-D canonical form).
run_cpu timeout -k 10 300 python - <<'EOF'
import tempfile
import jax, jax.numpy as jnp, numpy as np, optax
import horovod_tpu as hvd
from horovod_tpu import elastic, training
from horovod_tpu.optimizer import zero_to_canonical
from horovod_tpu.parallel import checkpoint as ckpt, create_hybrid_mesh
from horovod_tpu.parallel.transformer import (TransformerConfig,
                                              make_parallel_train_step)

hvd.init()
cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, dtype=jnp.float32,
                        unembed_dtype=jnp.float32, attn_backend="xla")
rng = np.random.RandomState(0)
tokens = jnp.asarray(rng.randint(0, 64, (8, 16)), jnp.int32)
labels = jnp.roll(tokens, -1, axis=1)

def run(mesh, **kw):
    init_state, step = make_parallel_train_step(cfg, mesh,
                                                optax.adam(1e-2), **kw)
    p, o = init_state(jax.random.PRNGKey(3))
    losses = []
    for _ in range(3):
        p, o, loss = step(p, o, tokens, labels)
        losses.append(float(loss))
    return losses, p, o, step

ref_losses, ref_p, _, _ = run(
    create_hybrid_mesh(dp=4, devices=jax.devices()[:4]),
    zero=True, wire_dtype="fp32")
hyb_losses, hyb_p, hyb_o, _ = run(
    create_hybrid_mesh(dp=2, tp=2, devices=jax.devices()[:4]),
    zero=True, overlap=True, wire_dtype="bf16")
np.testing.assert_allclose(hyb_losses, ref_losses, rtol=5e-3)
for a, b in zip(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, hyb_p)),
        jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, ref_p))):
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=4e-2)

d = tempfile.mkdtemp()
es = elastic.ElasticState(hyb_p, hyb_o, step=3, directory=d,
                          commit_every=1)
path = es.commit()
assert ckpt.verify_checkpoint(path) is True
canon = jax.tree_util.tree_map(np.asarray,
                               zero_to_canonical(hyb_o).inner)
mesh2 = create_hybrid_mesh(dp=4, tp=2)
init2, step2 = make_parallel_train_step(cfg, mesh2, optax.adam(1e-2),
                                        zero=True)
p2, o2 = init2(jax.random.PRNGKey(9))
assert o2.plan.nshards == 4
es2 = elastic.ElasticState(p2, o2, directory=d)
es2.restore()
assert es2.step == 3, es2.step
for a, b in zip(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        np.asarray, zero_to_canonical(es2.opt_state).inner)),
        jax.tree_util.tree_leaves(canon)):
    np.testing.assert_array_equal(a, b)
p3, o3, loss3 = step2(es2.params, es2.opt_state, tokens, labels)
assert np.isfinite(float(loss3))
print(f"hybrid smoke OK: (dp=2,tp=2) zero+overlap+bf16 matches dp=4 fp32 "
      f"over 3 steps, (2,2)->(4,2) restore bit-exact and resumed "
      f"(loss {float(loss3):.4f})")
EOF

echo "== 3-D smoke: dp×tp×pp pipelined train vs pure-dp reference (ISSUE 20) =="
# ISSUE 20 acceptance: a 3-step (dp=2,tp=2,pp=2) pipelined run with
# --overlap --wire-dtype bf16 must match the dp=8 fp32 reference (the
# NON-pipelined family, same global weights grafted across layouts)
# within the documented wire tolerance — every gradient plane
# interpreting the one spec-grouped GradSync plan.
run_cpu timeout -k 10 300 python - <<'EOF'
import jax, jax.numpy as jnp, numpy as np, optax
from horovod_tpu.parallel import create_hybrid_mesh
from horovod_tpu.parallel.pp_transformer import make_pp_transformer_train_step
from horovod_tpu.parallel.transformer import (TransformerConfig,
                                              make_parallel_train_step)

cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, dtype=jnp.float32,
                        unembed_dtype=jnp.float32, attn_backend="xla")
rng = np.random.RandomState(0)
tokens = jnp.asarray(rng.randint(0, 64, (8, 16)), jnp.int32)
labels = jnp.roll(tokens, -1, axis=1)

mesh3d = create_hybrid_mesh(dp=2, tp=2, pp=2)
init3d, step3d = make_pp_transformer_train_step(
    cfg, mesh3d, optax.adam(1e-2), n_microbatches=2,
    overlap=True, wire_dtype="bf16")
p, o = init3d(jax.random.PRNGKey(3))
src = jax.tree_util.tree_map(np.asarray, p)
losses3d = []
for _ in range(3):
    p, o, loss = step3d(p, o, tokens, labels)
    losses3d.append(float(loss))

# Same global weights on the dp=8 reference: unstack the [S, lps, ...]
# stage layout into the per-layer list the core family carries.
lps = cfg.n_layers // 2
flat = {"embed": src["embed"], "lnf": src["lnf"],
        "layers": [{k: src["stages"][k][s, i] for k in src["stages"]}
                   for s in range(2) for i in range(lps)]}
init8, step8 = make_parallel_train_step(cfg, create_hybrid_mesh(dp=8),
                                        optax.adam(1e-2))
p8, o8 = init8(jax.random.PRNGKey(9))
p8 = jax.tree_util.tree_map(
    lambda tpl, v: jax.device_put(jnp.asarray(v), tpl.sharding), p8, flat)
losses8 = []
for _ in range(3):
    p8, o8, loss = step8(p8, o8, tokens, labels)
    losses8.append(float(loss))
np.testing.assert_allclose(losses3d, losses8, rtol=5e-3)

ref = jax.tree_util.tree_map(np.asarray, p8)
back = {"embed": ref["embed"], "lnf": ref["lnf"],
        "stages": {k: np.stack([np.stack(
            [ref["layers"][s * lps + i][k] for i in range(lps)])
            for s in range(2)]) for k in src["stages"]}}
for a, b in zip(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, p)),
        jax.tree_util.tree_leaves(back)):
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=4e-2)
print(f"3-D smoke OK: (dp=2,tp=2,pp=2) overlap+bf16 matches dp=8 fp32 "
      f"over 3 steps (final loss {losses3d[-1]:.4f})")
EOF

echo "== plan smoke: env-world wires exactly the stamped plan's bytes (tpurun) =="
timeout -k 10 300 python -m horovod_tpu.launcher -np 2 --cpu \
  python tests/plan_worker.py

# Final sweep: launcher legs above write flight-recorder dumps into the
# repo root when they die mid-drill; a leftover would be committed by the
# next contributor's `git add -A`.
rm -f hvd_flightrec.rank*.json

echo "CI OK"
