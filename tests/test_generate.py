"""Generation-plane tests: prefill/decode parity against one-shot
``forward()``, continuous-batching invariance (a stream is bit-identical
alone vs joining a busy batch mid-flight), sampling reproducibility,
EOS/max-tokens/deadline/overload/drain semantics, quantized restore, and
the `/generate` streaming front end.

All CPU and deliberately tiny (the tier-1 budget is nearly full): one
module-scoped model, engines share its compiles where possible, and the
heavy open-loop load test lives in ci.sh (`serve_bench --mode generate`),
not here. Timing style per repo policy: generous waits, no elapsed-time
asserts.
"""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import serve
from horovod_tpu.exceptions import (DeadlineExceededError, ServerClosedError,
                                    ServerOverloadedError)
from horovod_tpu.parallel.transformer import (TransformerConfig,
                                              decode_step, forward,
                                              init_kv_cache, init_params,
                                              kv_cache_specs, prefill)

CFG = dict(vocab=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
           dtype=jnp.float32, unembed_dtype=jnp.float32,
           attn_backend="xla")


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(**CFG)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 16)
    kw.setdefault("default_max_new_tokens", 4)
    return serve.GenerationEngine(params, cfg,
                                  serve.GenerationConfig(**kw))


class TestModelLayer:
    def test_prefill_then_decode_matches_forward(self, model):
        """The parity contract: prefill logits match one-shot forward()
        at every prompt position, and each decode step's logits match
        forward() on the extended sequence at its last position."""
        cfg, params = model
        rng = np.random.RandomState(0)
        toks = rng.randint(0, cfg.vocab, (10,)).astype(np.int32)
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        ref = np.asarray(forward(params, toks[None], cfg, mesh)[0][0])

        cache = init_kv_cache(cfg, max_slots=3, max_len=16)
        cache, plog = jax.jit(
            lambda p, t, c: prefill(p, t, c, 1, cfg))(params, toks[:6],
                                                      cache)
        np.testing.assert_allclose(np.asarray(plog), ref[:6],
                                   rtol=1e-5, atol=1e-6)
        assert int(cache["lengths"][1]) == 6

        dec = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg))
        last = np.full((3,), 7, np.int32)       # inactive rows: garbage
        pos = np.full((3,), -1, np.int32)
        for i in range(6, 10):
            last[1] = toks[i]
            pos[1] = i
            cache, dlog = dec(params, last, cache, pos)
            np.testing.assert_allclose(np.asarray(dlog)[1], ref[i],
                                       rtol=1e-5, atol=1e-6)
        assert int(cache["lengths"][1]) == 10

    def test_prefill_with_padding_matches_unpadded(self, model):
        """A padded prompt bucket (the engine's compile-cache shape) gives
        the same logits at real positions — pad K/V are causally ahead."""
        cfg, params = model
        toks = np.arange(5, dtype=np.int32)
        cache = init_kv_cache(cfg, 1, 16)
        _, lp = jax.jit(lambda p, t, c: prefill(p, t, c, 0, cfg))(
            params, toks, cache)
        padded = np.zeros((8,), np.int32)
        padded[:5] = toks
        _, lq = jax.jit(
            lambda p, t, c: prefill(p, t, c, 0, cfg, length=5))(
            params, padded, cache)
        np.testing.assert_allclose(np.asarray(lq)[:5], np.asarray(lp),
                                   rtol=1e-6, atol=1e-7)

    def test_kv_cache_specs_shard_heads_over_tp(self, model):
        cfg, _ = model
        devs = jax.devices()
        mesh = Mesh(np.array(devs[:2]).reshape(1, 2), ("dp", "tp"))
        specs = kv_cache_specs(cfg, mesh)
        assert specs["k"] == P(None, None, None, "tp", None)
        assert specs["v"] == P(None, None, None, "tp", None)
        assert specs["lengths"] == P()
        cache = init_kv_cache(cfg, 2, 8)
        assert cache["k"].shape == (cfg.n_layers, 2, 8, cfg.n_heads,
                                    cfg.d_model // cfg.n_heads)

    def test_moe_rejected(self):
        cfg = TransformerConfig(**{**CFG, "n_experts": 2})
        with pytest.raises(NotImplementedError, match="dense"):
            init_kv_cache(cfg, 1, 8)


class TestContinuousBatching:
    def test_mid_flight_join_bit_identical(self, model):
        """THE invariance contract: a request's stream is bit-identical
        whether it runs alone or joins a busy batch mid-flight (slot rows
        are numerically independent and the decode shape is fixed)."""
        cfg, params = model
        eng = _engine(params, cfg, max_slots=3, max_len=16,
                      default_max_new_tokens=6)
        try:
            prompt = [3, 1, 4, 1, 5]
            samp = serve.SamplingParams(temperature=0.7, top_k=8, seed=11)
            alone = eng.generate(prompt, timeout=60, sampling=samp)
            # Two long-running neighbors keep the batch busy...
            busy = [eng.submit([9, 9], max_new_tokens=11),
                    eng.submit([8, 8, 8], max_new_tokens=11)]
            time.sleep(0.05)    # ...so the probe joins mid-flight
            joined = eng.generate(prompt, timeout=60, sampling=samp)
            assert joined["tokens"] == alone["tokens"]
            assert joined["finish_reason"] == alone["finish_reason"]
            for h in busy:
                assert h.result(60)["n_tokens"] == 11
        finally:
            eng.shutdown()

    def test_slots_recycle_and_fill_metric(self, model):
        cfg, params = model
        eng = _engine(params, cfg, max_slots=2, default_max_new_tokens=3)
        try:
            outs = [eng.submit([i + 1], max_new_tokens=3)
                    for i in range(5)]
            assert all(h.result(60)["n_tokens"] == 3 for h in outs)
            snap = eng.stats()
            assert snap["generation"]["generations_total"] == 5
            assert snap["generation"]["tokens_generated_total"] == 15
            assert 0.0 < snap["batch_fill_ratio"] <= 1.0
            assert snap["active_slots"] == 0
            json.dumps(snap)     # /stats wire format must round-trip
        finally:
            eng.shutdown()


class TestSamplingAndTermination:
    @pytest.fixture(scope="class")
    def eng(self, model):
        cfg, params = model
        e = _engine(params, cfg, max_slots=2, max_len=16,
                    default_max_new_tokens=4)
        yield e
        e.shutdown()

    def test_greedy_is_deterministic(self, eng):
        a = eng.generate([1, 2, 3], timeout=60)
        b = eng.generate([1, 2, 3], timeout=60)
        assert a["tokens"] == b["tokens"]
        assert a["finish_reason"] == "length"

    def test_seeded_sampling_reproducible_and_seed_sensitive(self, eng):
        s = serve.SamplingParams(temperature=0.9, top_k=5, seed=7)
        a = eng.generate([2, 4], timeout=60, max_new_tokens=8, sampling=s)
        b = eng.generate([2, 4], timeout=60, max_new_tokens=8, sampling=s)
        assert a["tokens"] == b["tokens"]
        streams = {tuple(eng.generate(
            [2, 4], timeout=60, max_new_tokens=8,
            sampling=serve.SamplingParams(temperature=0.9, top_k=5,
                                          seed=seed))["tokens"])
            for seed in range(5)}
        assert len(streams) > 1     # temperature actually samples

    def test_eos_terminates(self, eng):
        # EOS = the first greedy token (past index 0) that has not occurred
        # earlier in the stream, so the test pins termination AT that
        # index, not one numeric stream.
        ref = eng.generate([1, 2, 3], timeout=60, max_new_tokens=12)
        toks = ref["tokens"]
        i = next(j for j in range(1, len(toks)) if toks[j] not in toks[:j])
        r = eng.generate([1, 2, 3], timeout=60, max_new_tokens=12,
                         eos_id=toks[i])
        assert r["finish_reason"] == "eos"
        assert r["tokens"] == toks[:i + 1]
        assert r["n_tokens"] == i + 1

    def test_max_tokens_and_cache_capacity_clamp(self, eng):
        r = eng.generate([1] * 14, timeout=60, max_new_tokens=50)
        # 14-token prompt in a 16-deep cache: positions 14, 15 take the
        # next two K/V writes, the third sampled token needs no write.
        assert r["finish_reason"] == "length"
        assert r["n_tokens"] == 3

    def test_streaming_iterator(self, eng):
        h = eng.submit([5, 6], max_new_tokens=3)
        toks = list(h)
        assert toks == h.result(10)["tokens"]
        assert len(toks) == 3

    def test_submit_validation(self, eng):
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit([])
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(list(range(17)))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1], max_new_tokens=0)


class TestBackpressure:
    def test_deadline_expires_in_queue(self, model):
        cfg, params = model
        eng = _engine(params, cfg, max_slots=1, max_len=16)
        try:
            # One slot, one long stream: the second request waits queued
            # past its 1 ms deadline and must fail at slot admission.
            long = eng.submit([9, 9], max_new_tokens=15)
            h = eng.submit([1, 2], deadline_ms=1.0)
            with pytest.raises(DeadlineExceededError):
                h.result(60)
            assert long.result(60)["n_tokens"] == 15
            snap = eng.stats()
            assert snap["expired_deadline"] == 1
        finally:
            eng.shutdown()

    def test_overload_rejection(self, model):
        cfg, params = model
        eng = _engine(params, cfg, max_slots=1, max_queue=1,
                      default_max_new_tokens=12)
        try:
            accepted = [eng.submit([7])]
            rejected = 0
            for _ in range(6):
                try:
                    accepted.append(eng.submit([7]))
                except ServerOverloadedError:
                    rejected += 1
            assert rejected >= 1
            assert eng.stats()["rejected_overload"] == rejected
            for h in accepted:
                assert h.result(60)["n_tokens"] == 12
        finally:
            eng.shutdown()

    def test_graceful_drain_finishes_admitted(self, model):
        cfg, params = model
        eng = _engine(params, cfg, max_slots=2, default_max_new_tokens=5)
        handles = [eng.submit([i + 1], max_new_tokens=5) for i in range(4)]
        eng.shutdown(drain=True)
        assert all(h.result(60)["n_tokens"] == 5 for h in handles)
        assert not eng._thread.is_alive()
        with pytest.raises(ServerClosedError):
            eng.submit([1])

    def test_nondrain_shutdown_fails_pending(self, model):
        cfg, params = model
        eng = _engine(params, cfg, max_slots=1,
                      default_max_new_tokens=200, max_len=250)
        h0 = eng.submit([9])            # occupies the only slot, long
        h1 = eng.submit([1, 2])         # stays queued
        eng.shutdown(drain=False)
        with pytest.raises(ServerClosedError):
            h1.result(30)
        with pytest.raises(ServerClosedError):
            h0.result(30)
        eng.shutdown()                  # idempotent


class TestRestoreDtype:
    @pytest.fixture(scope="class")
    def ckpt_dir(self, model, tmp_path_factory):
        # One orbax write shared by every dtype test (budget).
        import optax
        from horovod_tpu.trainer import save_checkpoint
        from horovod_tpu.training import TrainState
        _, params = model
        st = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                        opt_state=optax.sgd(0.1).init(params))
        d = str(tmp_path_factory.mktemp("gen_ckpt"))
        save_checkpoint(d, st, step=1)
        return d

    def test_unknown_dtype_rejected_eagerly(self, tmp_path):
        # Eager: the named rejection fires before any checkpoint I/O
        # (the directory doesn't even exist).
        with pytest.raises(ValueError, match=r"int8"):
            serve.restore_for_inference(str(tmp_path / "nope"),
                                        dtype="fp16")

    def test_bf16_cast(self, model, ckpt_dir):
        v = serve.restore_for_inference(ckpt_dir, dtype="bf16")
        assert v["params"]["embed"].dtype == jnp.bfloat16
        # int leaves (none here) and structure survive; values round-trip
        # to bf16 precision
        np.testing.assert_allclose(
            np.asarray(v["params"]["lnf"], np.float32),
            np.asarray(model[1]["lnf"]), rtol=1e-2)

    def test_int8_roundtrip_verifies_and_generates(self, model, ckpt_dir):
        """The int8 contract: manifest CRCs are checked on the stored
        fp32 leaves (verify_checkpoint passes before AND after a
        quantized restore), matmul weights come back as QuantizedTensor,
        and the generation forward dequantizes them in-jit."""
        import os
        from horovod_tpu.ops.quant import QuantizedTensor
        from horovod_tpu.parallel.checkpoint import verify_checkpoint
        cfg, params = model
        path = os.path.join(ckpt_dir, "ckpt_1")
        assert verify_checkpoint(path) is True
        v = serve.restore_for_inference(ckpt_dir, dtype="int8")
        qp = v["params"]
        assert isinstance(qp["embed"], QuantizedTensor)
        assert qp["embed"].q.dtype == np.int8
        assert qp["lnf"].dtype == np.float32        # 1-D stays fp32
        assert verify_checkpoint(path) is True      # stored bytes intact
        # Quantization error is bounded by one step per channel.
        deq = np.asarray(qp["embed"].q, np.float32) * qp["embed"].scale
        ref = np.asarray(params["embed"])
        step = np.abs(ref).max(axis=0) / 127.0
        assert np.all(np.abs(deq - ref) <= step + 1e-7)
        # And the engine serves it end to end.
        eng = _engine(qp, cfg, max_slots=1, default_max_new_tokens=3)
        try:
            assert eng.generate([1, 2, 3], timeout=60)["n_tokens"] == 3
        finally:
            eng.shutdown()


@pytest.mark.slow
class TestHttpGenerate:
    """HTTP end-to-end drills: `slow`-marked to spare the tier-1 budget
    (~2s of engine warmups + sockets); ci.sh's generation leg runs this
    module WITHOUT the marker filter, so they stay gated."""

    def test_streaming_and_nonstreaming(self, model):
        cfg, params = model
        eng = _engine(params, cfg, default_max_new_tokens=4)
        try:
            with serve.HttpServer(generate=eng) as srv:
                url = f"http://{srv.host}:{srv.port}"
                ref = eng.generate([1, 2, 3], timeout=60)
                req = urllib.request.Request(
                    url + "/generate",
                    data=json.dumps({"tokens": [1, 2, 3]}).encode())
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 200
                    lines = [json.loads(line)
                             for line in resp.read().splitlines()]
                # one chunked JSON line per token, then the terminal line
                assert [ln["token"] for ln in lines[:-1]] == ref["tokens"]
                assert lines[-1]["done"] is True
                assert lines[-1]["tokens"] == ref["tokens"]
                assert lines[-1]["finish_reason"] == ref["finish_reason"]

                req = urllib.request.Request(
                    url + "/generate",
                    data=json.dumps({"tokens": [1, 2, 3],
                                     "stream": False,
                                     "seed": 3}).encode())
                with urllib.request.urlopen(req, timeout=30) as resp:
                    body = json.loads(resp.read())
                assert body["tokens"] == ref["tokens"]

                # /stats carries the generation block; /healthz warms
                with urllib.request.urlopen(url + "/stats",
                                            timeout=30) as resp:
                    snap = json.loads(resp.read())
                assert snap["generation"]["generations_total"] >= 3
                assert snap["latency_ms"]["ttft_p50"] is not None

                # bad request → 400; /predict has no engine here → 404
                req = urllib.request.Request(url + "/generate",
                                             data=b'{"nope": 1}')
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 400
                req = urllib.request.Request(url + "/predict", data=b"{}")
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 404
        finally:
            eng.shutdown()

    def test_healthz_readiness_lifecycle(self, model):
        cfg, params = model
        # max_len=4 keeps warmup() to three prefill buckets (budget).
        eng = _engine(params, cfg, max_slots=1, max_len=4,
                      default_max_new_tokens=2)

        def probe(url):
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            with serve.HttpServer(generate=eng) as srv:
                url = f"http://{srv.host}:{srv.port}"
                code, body = probe(url)
                assert code == 503 and body["status"] == "warming"
                eng.warmup()
                code, body = probe(url)
                assert code == 200 and body["status"] == "ok"
                eng.shutdown()
                code, body = probe(url)
                assert code == 503 and body["status"] == "draining"
        finally:
            eng.shutdown()
