"""Family ``lm_kda_mla_moe``: a decoder of layers of two kinds (Kimi Delta
Attention, a delta rule whose decay is per key channel, and latent
attention without positions), the first ending in a dense feed-forward and
the others in a top-k mixture of gated experts under sigmoid scores of
which this chip holds a share, plus one ungated shared expert, through
``make_parallel_train_step`` — the same step builder, optimizer and
donation as families ``lm``, ``lm_moe_dsa`` and ``lm_gdn_moe``, whose
driver interface (pool, step, routing gauges after the window) this one
inherits. The configuration's file names the sizes with the source's
(Hugging Face) keys, its ``reads`` group says which key counts what is held
here, and its ``training`` group holds what the source does not say.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from families import lm_moe_dsa
from lib import after_window
from reference import lm_kda_mla_moe as reference

RATE_METRIC = "tokens_per_s_per_chip"
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# `correct`: the system's training forward (bf16 activations, the chunked
# rule with its Pallas kernels, the flash kernels at 192 / 128, bf16
# unembedding, float32 accumulation) against the float32 reference (the
# recurrence token by token, blocked float32 softmax attention) RUN ON THE
# SYSTEM'S OWN ROUTING SETS, on the pool's one seeded sequence at the timed
# length: (a) per-token NLL, mean |difference| and difference of the means;
# (b) per delta-attention layer, the rule's output o: mean over rows and
# heads of |difference| over the mean of |reference|; (c) the same measure
# of the latent-attention layer's attention output; (d) the system's
# routing sets against the reference's own (top-8 of 256 from its float32
# scores): the share of each token's reference set that the system also
# chose, mean over tokens, in the worst layer. And the BACKWARD the step
# runs, which no forward shows: (e) the first delta-attention layer's rule
# and (f) the latent attention, each from the inputs the system's forward
# gave it under one seeded cotangent: the system's gradients (the kernels'
# written-out backward: dg a channel, dq and dk cut back from the padded
# 256 to 192) against ``jax.vjp`` of the float32 recurrence / blocked
# attention, mean |difference| over mean |reference| a gradient.
#
# Measured on the chip (my chip runs, PR 34; PERF.md section 6), two dozen
# seeds, the configuration as it stands: mean |NLL difference| 0.00801-
# 0.00821, mean losses at most 2.9e-4 apart, o off by 0.0063 / 0.0091 /
# 0.0113 / 0.0149 of its mean size in the four delta-attention layers (to
# three digits on every seed: bf16's rounding, carried from layer to
# layer), the latent attention's output by 0.0056-0.0065, 98.71-98.94% of
# each token's routing set in common in the worst layer. A wrong block or a
# lower precision, on the same weights, two or three seeds each (NLL / o in
# the worst delta layer / the latent attention's output / sets in common in
# the worst layer; ``tests/benchmark/kda_mla_moe_controls.py`` puts each in
# the program's place and this check itself says not correct): the shared
# key part left out of the score 0.0162-0.0164 / 0.030 / 0.17-0.18 / 97.7%;
# softmax in place of sigmoid scores 0.027-0.029 / 0.051-0.056 / 0.017 /
# 96.5-96.8%; the decay taken per head (its mean over the channels, the
# scalar rule) 0.038-0.045 / 0.082-0.093 / 0.035-0.041 / 94.2-94.9%; the
# decay left out 0.73 / 14.5 / 1.3 / 27%; every norm's output at
# float8_e4m3's three bits of mantissa (the nearest precision below bf16)
# 0.0492-0.0494 / 0.088-0.089 / 0.032-0.033 / 93.7-93.8% (through the
# float8_e4m3 type itself: 0.0465-0.0473 / 0.084 / 0.028-0.032 / 93.8-
# 94.2%). Each limit lies between the block's reading and the lower
# precision's with room on both sides, and where that leaves room, under
# the nearest wrong block's too: the NLL's 1.46 times over the first and
# 1.35 under the missing key part's (3.9 under fp8's least); o's 1.41 times
# over the first and 1.42 under the missing key part's (4.0 under fp8's);
# the latent attention's 1.54 times over the first and 1.7 under softmax
# scores' (2.8 under fp8's); the sets' miss rate 2.3 times over the first
# and 1.9 under fp8's. The mean loss barely moves for a lower precision
# (random labels), so it keeps the other LM cells' limit, 7 times over the
# first reading.
#
# The gradients (a dozen seeds; PERF.md section 6 has the lines): as it
# stands the rule's dq, dk 0.0031-0.0032, dv 0.0027, dbeta 0.0028, the
# attention's dq 0.0028-0.0029, dk 0.0033, dv 0.0032-0.0033: bf16's
# rounding of the operands; the rule's dg 0.0199-0.0278, eight times
# those: each channel's dg is a difference of two sums of bf16 products.
# Wrong blocks whose FORWARD is the block's: dg as a gate per head would
# have it (its mean over the channels) 1.19-1.27 on dg alone; the shared
# key part's columns of dq and dk dropped 0.257-0.259 / 0.335 on the
# attention's dq / dk alone. Every other check passes those two: only (e)
# and (f) see them. The decay taken per head reads 0.029-0.032 on the
# rule's dq, dk, dv, dbeta and 1.03 on dg. The lower precision, here the
# mixers' operands at float8_e4m3's three bits of mantissa: the rule's dq,
# dk 0.043, dv 0.033, dbeta 0.038, dg 0.055-0.061; the attention's 0.042 /
# 0.059 / 0.045. Limits: 0.009 for the rule's four and the attention's
# three, 2.7 times over the largest reading as it stands and 3.7 under
# fp8's least; 0.04 for dg, 1.44 times over and 1.37 under.
TOL_MEAN_ABS_TOKEN = 0.012     # mean |NLL difference| per token
TOL_MEAN_LOSS = 0.002          # |difference of the mean NLLs|
TOL_KDA_O_REL = 0.021          # per delta-attention layer, mean |do| / mean |o|
TOL_MLA_O_REL = 0.010          # the latent-attention layer, the same measure
MIN_ROUTING_OVERLAP = 0.97     # mean share of a token's set in common
TOL_RULE_GRAD_REL = 0.009      # the rule's dq, dk, dv, dbeta
TOL_RULE_DG_REL = 0.04         # the rule's dg [.., dk]
TOL_ATTEND_GRAD_REL = 0.009    # the latent attention's dq, dk, dv


def hlo_counts(compiled, seq_len: int = 0, n_heads: int = 0) -> dict:
    """Pallas kernels, by all and by name (the rule's four, the flash
    kernels), all-reduces, and arrays [.., n_heads, seq_len, seq_len]
    (scores that left the kernels) in a compiled program's text. The heads
    are asked for too: at 8192 rows the latent's expansion, 32 heads x
    (128 + 128) columns a row, is itself an [8192, 8192] array."""
    hlo = compiled.as_text()

    def named(prefix):
        return len(re.findall(rf"%{prefix}[\w.]* = ", hlo))
    square = re.findall(
        rf"\[(?:\d+,)*{n_heads},{seq_len},{seq_len}\]", hlo) \
        if seq_len else []
    return {"tpu_custom_call": hlo.count("tpu_custom_call"),
            "kda_local_kernels": named("kda_local_"),
            "kda_walk_kernels": named("kda_fwd") + named("kda_bwd"),
            "flash_kernels": named("flash_"),
            "score_arrays": len(square),
            "all-reduce": hlo.count("all-reduce(")
            + hlo.count("all-reduce-start(")}


def layer_kinds(c: dict):
    """The kind of each layer held, from ``linear_attn_config``'s lists
    (layers counted from 1, as published)."""
    lin = c["linear_attn_config"]
    kinds = {i: "kda" for i in lin["kda_layers"]}
    kinds.update({i: "mla" for i in lin["full_attn_layers"]})
    return tuple(kinds[i + 1] for i in range(c["num_hidden_layers"]))


def model_config(c: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from horovod_tpu.parallel.transformer import (
        KimiDeltaAttention, LatentAttention, TransformerConfig)
    tr, lin = c["training"], c["linear_attn_config"]
    return TransformerConfig(
        vocab=c["vocab_rows_held"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        mlp="swiglu", tied_head=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], layer_pattern=layer_kinds(c),
        kda=KimiDeltaAttention(
            lin["num_heads"], lin["head_dim"],
            conv_width=lin["short_conv_kernel_size"], chunk=tr["kda_chunk"],
            backend=tr["kda_backend"]),
        mla=LatentAttention(c["kv_lora_rank"], c["qk_nope_head_dim"],
                            c["qk_rope_head_dim"], c["v_head_dim"]),
        dense_layers=min(c["first_k_dense_replace"], c["num_hidden_layers"]),
        dense_ff=c["intermediate_size"],
        d_ff=c["moe_intermediate_size"], n_experts=c["router_experts"],
        moe_top_k=c["num_experts_per_token"],
        moe_renormalize=c["moe_renormalize"],
        moe_score=c["moe_router_activation_func"], moe_select_bias=True,
        moe_scale=c["routed_scaling_factor"],
        experts_held=c["num_experts"], first_expert=c["first_expert"],
        shared_expert_ff=c["moe_intermediate_size"]
        * c["num_shared_experts"], shared_expert_gate=False,
        dtype=_DTYPES[tr["activation_dtype"]],
        attn_backend=tr["attn_backend"],
        unembed_dtype=_DTYPES[tr["unembed_dtype"]], remat=tr["remat"],
        loss_chunk=tr["loss_chunk"])


def reference_sizes(c: dict) -> dict:
    lin = c["linear_attn_config"]
    return {"n_heads": c["num_attention_heads"],
            "kv_rank": c["kv_lora_rank"], "d_nope": c["qk_nope_head_dim"],
            "d_shared": c["qk_rope_head_dim"], "d_v": c["v_head_dim"],
            "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "experts_per_tok": c["num_experts_per_token"],
            "first_expert": c["first_expert"],
            "scaling": c["routed_scaling_factor"], "eps": c["rms_norm_eps"]}


def _rel(got, want):
    return jnp.mean(jnp.abs(got.astype(jnp.float32) - want)) \
        / jnp.mean(jnp.abs(want))


def _gradient_gaps(fn, plain, inputs, key):
    """``fn``'s backward against the float32 ``plain``'s, from the same
    inputs under one seeded cotangent: per input, mean |difference of the
    gradients| over mean |the reference's|."""
    out, pull = jax.vjp(fn, *inputs)
    cot = jax.random.normal(key, out.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.vjp(plain, *(x.astype(jnp.float32) for x in inputs))[1](cot)
    return jnp.stack([_rel(a, b)
                      for a, b in zip(pull(cot.astype(out.dtype)), want)])


class Family(lm_moe_dsa.Family):
    """Family ``lm_moe_dsa``'s pool, step and routing gauges around another
    model and another check. The routing gauges' ``layer`` counts the
    EXPERT layers from 0 (the leading dense layer has no router)."""

    def __init__(self, ctx):
        from horovod_tpu.parallel.mesh import create_hybrid_mesh
        from horovod_tpu.parallel.transformer import make_parallel_train_step
        t, o = ctx.traffic, ctx.config["training"]["optimizer"]
        self.ctx = ctx
        self.mesh = create_hybrid_mesh(devices=ctx.devices,
                                       **t.get("mesh", {"dp": ctx.chips}))
        self.cfg = model_config(ctx.config)
        # The peak rate is reached by a linear warm-up, as a pre-training
        # run's first steps are; no balance loss: the configuration gives
        # no coefficient.
        self.init_state, self._step = make_parallel_train_step(
            self.cfg, self.mesh, optax.adamw(
                optax.linear_schedule(0.0, o["lr"], o["warmup_steps"]),
                b1=o["b1"], b2=o["b2"], weight_decay=o["weight_decay"]),
            aux_weight=0.0)
        self.batch = t["batch_per_chip"] * ctx.chips
        self.seq_len = t["seq_len"]
        self.units_per_step = self.batch * self.seq_len
        self.batch_sharding = NamedSharding(self.mesh, P("dp", None))
        self.compiled = None
        self._pool, self._params, self._system = [], None, None

    def compile(self, state, batch):
        from horovod_tpu.ops.gated_delta import resolve_backend
        with self.ctx.compiling("train_step"):
            self.compiled = self.lower(state, batch).compile()
        counts = hlo_counts(self.compiled, self.seq_len, self.cfg.n_heads)
        backend = resolve_backend(self.cfg.kda.backend)
        self.ctx.log(event="compiled_step", kda_backend=backend, **counts)
        if jax.devices()[0].platform != "tpu":
            return
        if backend == "pallas" and not (counts["kda_local_kernels"]
                                        and counts["kda_walk_kernels"]):
            raise RuntimeError("no kda_* kernel in the compiled step: the "
                               "rule's kernels are not in it")
        if self.cfg.attn_backend == "pallas" and not counts["flash_kernels"]:
            raise RuntimeError("no flash_* kernel in the compiled step: "
                               "the latent attention's kernels are not in it")
        if counts["score_arrays"]:
            raise RuntimeError(
                f"{counts['score_arrays']} arrays [.., {self.cfg.n_heads}, "
                f"{self.seq_len}, {self.seq_len}] in the compiled step: "
                f"scores left a kernel")

    # -- correctness --------------------------------------------------------

    def backward_gaps(self, cfg):
        """(e) and (f) of ``reference_check`` as one function of the first
        delta-attention layer's (q, k, v, g, beta) and the latent
        attention's (q, k, v), as the system's forward made them."""
        def backward(kda_in, mla_in):
            # The modules' attributes as the mixers look them up, so that
            # a builder's wrong block reaches these calls too.
            from horovod_tpu.ops import gated_delta, pallas_attention
            keys = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(self.ctx.seed % (2 ** 31)), 1))
            remat = 64 if self.seq_len % 64 == 0 else 0
            return {
                "rule": _gradient_gaps(
                    lambda *a: gated_delta.gated_delta_rule(
                        *a, chunk=cfg.kda.chunk, backend=cfg.kda.backend),
                    lambda *a: reference.delta_rule(*a, remat_rows=remat),
                    kda_in, keys[0]),
                "attend": _gradient_gaps(
                    lambda *a: pallas_attention.flash_attention(
                        *a, causal=True, backend=cfg.attn_backend,
                        fallback=False),
                    lambda *a: reference.attention(*a, q_block=256),
                    mla_in, keys[1])}
        return backward

    def reference_check(self, state, cfg=None) -> bool:
        """Parts (a) to (f) above. ``cfg`` (a builder's tool, never the
        harness's: ``tests/benchmark/kda_mla_moe_controls.py``) checks
        another block than the configuration's against the same
        reference: PERF.md shows wrong ones failing."""
        from horovod_tpu.parallel.moe import record_routing
        from horovod_tpu.parallel.transformer import (dense_nll,
                                                      forward_with_stats)
        cfg = self.cfg if cfg is None else cfg
        params = state[0]
        rng = np.random.default_rng(self.ctx.seed + 1)
        n = self.ctx.traffic.get("reference_sequences", 1)
        tok = rng.integers(0, cfg.vocab, size=(n, self.seq_len + 1),
                           dtype=np.int32)
        tokens, labels = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])
        sizes = reference_sizes(self.ctx.config)

        def system(p, t, l):
            logits, layers = forward_with_stats(p, t, cfg, self.mesh)
            routed = [e for e in layers if "ids" in e]
            return {"nll": dense_nll(logits, l),
                    "kda_o": [e["kda_o"] for e in layers if "kda_o" in e],
                    "mla_o": [e["mla_o"] for e in layers if "mla_o" in e],
                    "kda_in": next(e["kda_in"] for e in layers
                                   if "kda_in" in e),
                    "mla_in": next(e["mla_in"] for e in layers
                                   if "mla_in" in e),
                    "ids": [e["ids"] for e in routed],
                    "held_load": jnp.stack([e["held_load"] for e in routed]),
                    "absent": jnp.stack([e["absent"] for e in routed])}

        def plain(p, t, l, ids, kda_o, mla_o):
            out = reference.forward(p, t, l, sizes, routing=ids)
            common = [jnp.mean(jnp.any(
                own[:, :, None] == given[:, None, :], axis=-1))
                for own, given in zip(out["routed"], ids)]
            return {"nll": out["nll"], "overlap": jnp.stack(common),
                    "kda_o_rel": jnp.stack([
                        _rel(a, b) for a, b in zip(kda_o, out["kda_o"])]),
                    "mla_o_rel": jnp.stack([
                        _rel(a, b) for a, b in zip(mla_o, out["mla_o"])])}

        system = jax.jit(system)
        if cfg is self.cfg:
            self._system = system
        with self.ctx.compiling("reference_check"):
            got = system(params, tokens, labels)
            want = jax.device_get(jax.jit(plain)(
                params, tokens, labels, got["ids"], got["kda_o"],
                got["mla_o"]))
            grads = jax.device_get(jax.jit(self.backward_gaps(cfg))(
                got.pop("kda_in"), got.pop("mla_in")))
        got = jax.device_get({k: got[k] for k in ("nll", "held_load",
                                                  "absent")})
        for li in range(len(got["held_load"])):
            record_routing(li, got["held_load"][li], got["absent"][li])

        rule = dict(zip(("q", "k", "v", "g", "beta"),
                        map(float, grads["rule"])))
        attend = dict(zip(("q", "k", "v"), map(float, grads["attend"])))
        token_err = float(np.mean(np.abs(got["nll"] - want["nll"])))
        loss_err = float(abs(got["nll"].mean() - want["nll"].mean()))
        ok = bool(np.all(np.isfinite(got["nll"]))
                  and token_err <= TOL_MEAN_ABS_TOKEN
                  and loss_err <= TOL_MEAN_LOSS
                  and float(want["kda_o_rel"].max()) <= TOL_KDA_O_REL
                  and float(want["mla_o_rel"].max()) <= TOL_MLA_O_REL
                  and float(want["overlap"].min()) >= MIN_ROUTING_OVERLAP
                  # (each by itself: a NaN is under no limit)
                  and all(rule[n] <= TOL_RULE_GRAD_REL
                          for n in ("q", "k", "v", "beta"))
                  and rule["g"] <= TOL_RULE_DG_REL
                  and all(x <= TOL_ATTEND_GRAD_REL for x in attend.values()))
        self.ctx.log(
            event="reference_check", ok=ok,
            system_loss=float(got["nll"].mean()),
            reference_loss=float(want["nll"].mean()),
            mean_abs_token_err=token_err, tol_mean_abs_token=TOL_MEAN_ABS_TOKEN,
            max_abs_token_err=float(np.max(np.abs(got["nll"] - want["nll"]))),
            mean_loss_err=loss_err, tol_mean_loss=TOL_MEAN_LOSS,
            kda_o_rel_err=[float(x) for x in want["kda_o_rel"]],
            tol_kda_o_rel=TOL_KDA_O_REL,
            mla_o_rel_err=[float(x) for x in want["mla_o_rel"]],
            tol_mla_o_rel=TOL_MLA_O_REL,
            routing_overlap=[float(x) for x in want["overlap"]],
            min_routing_overlap=MIN_ROUTING_OVERLAP,
            rule_grad_rel_err=rule, tol_rule_grad_rel=TOL_RULE_GRAD_REL,
            tol_rule_dg_rel=TOL_RULE_DG_REL, attend_grad_rel_err=attend,
            tol_attend_grad_rel=TOL_ATTEND_GRAD_REL,
            held_load=[[int(v) for v in row] for row in got["held_load"]],
            absent_assignments=[int(v) for v in got["absent"]])
        return ok


def build(ctx) -> Family:
    family = Family(ctx)
    after_window.HOOKS.append(family.stamp_routing)
    return family
