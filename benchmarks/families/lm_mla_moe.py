"""Family ``lm_mla_moe``: the DeepSeek-V3 block (``model_type``
``deepseek_v3``) — latent attention on every layer, its decoupled key
part rotated by position, a leading dense feed-forward and after it a top-k
mixture of gated experts under sigmoid scores of which this chip holds a
share, beside ungated shared experts — through ``make_parallel_train_step``.
Family ``lm_kda_mla_moe``'s ``Family`` (pool, placement, step, routing
gauges after the window) and its check's helpers, around another model and
another check. The configuration's file names the sizes with the source's
(Hugging Face) keys, its ``reads`` group says which key counts what is held
here, and its ``training`` group holds what the source does not say.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from families import lm_kda_mla_moe as kimi
from lib import after_window
from reference import lm_mla_moe as reference

RATE_METRIC = "tokens_per_s_per_chip"

# `correct`: the system's training forward (bf16 activations, the rotation,
# the flash kernels at 192 / 128, bf16 unembedding, float32 accumulation)
# against the float32 reference (blocked float32 softmax attention, the
# rotation of the published interleaved pairs on the weights' published
# layout) RUN ON THE SYSTEM'S OWN ROUTING SETS, on the pool's one seeded
# sequence at the timed length: (a) per-token NLL, mean |difference| and
# difference of the means; (b) per layer, the attention's output: mean over
# rows and heads of |difference| over the mean of |reference|; (c) the
# system's routing sets against the reference's own (top-6 of 128 from its
# float32 scores): the share of each token's reference set that the system
# also chose, mean over tokens, in the worst layer. And the BACKWARD the
# step runs, which no forward shows: (d) the first layer's attention call,
# from the (rotated) q, k, v the system's forward gave it, under a
# cotangent drawn from the seed: the kernels' gradients (dq and dk cut back
# from the padded 256 to 192) against ``jax.vjp`` of the float32 blocked
# attention, mean |difference| over mean |reference| a gradient.
#
# Measured on the chip (my chip runs, PR 38; PERF.md section 6), the
# configuration as it stands, ten seeds: mean |NLL difference| 0.00742-
# 0.00760, mean losses at most 2.5e-4 apart, the attention's output off by
# 0.0060-0.0061 in the first layer to 0.0083-0.0091 in the last (bf16's
# rounding, carried from layer to layer), 98.99-99.12% of each token's
# routing set in common in the worst layer, the attention's dq 0.0028, dk
# and dv 0.0033. A
# wrong block or a lower precision, on the same weights (NLL / the
# attention's output in the worst layer / sets in common in the worst
# layer; ``tests/benchmark/mla_moe_controls.py`` puts each in the
# program's place and this check itself says not correct): the rotation
# left out 0.0447 / 0.389 / 94.5%; the published columns rotated as the
# program's pairs (the wrong pairs) 0.0484 / 0.422 / 94.0%; base 1e4 for
# 1e6 0.0476 / 0.411 / 94.2%; q rotated and the shared key part not 0.0456
# / 0.388 / 94.4%; the attention's operands at float8_e4m3's three bits of
# mantissa (the nearest precision below bf16) 0.0089 / 0.036 / 98.8%, and
# its dq, dk, dv 0.044 / 0.059 / 0.045; every norm's output at the same
# precision 0.0429 / 0.049 / 94.6%. The shared key part's columns of dq and
# dk dropped from the backward (the forward is the block's) read 0.237 /
# 0.336 on dq / dk alone: only (d) sees it. Limits: the NLL's 1.6 times
# over the largest reading and 3.6 under fp8 norm outputs' (the attention's
# operands at fp8 hardly move it), 3.7 under the nearest wrong block's; the
# attention's output 1.8 times over and 2.3 under fp8 operands'; the sets'
# miss rate 2.5 times over the largest and 2.1 under fp8 norm outputs';
# the gradients 2.7 times over and 4.8 under fp8's least. The mean loss
# barely moves for any of them (random labels), so it keeps the other LM
# cells' limit, 7.9 times over the largest reading.
TOL_MEAN_ABS_TOKEN = 0.012     # mean |NLL difference| per token
TOL_MEAN_LOSS = 0.002          # |difference of the mean NLLs|
TOL_MLA_O_REL = 0.016          # per layer, mean |do| / mean |o|
MIN_ROUTING_OVERLAP = 0.975    # mean share of a token's set in common
TOL_ATTEND_GRAD_REL = 0.009    # the attention's dq, dk, dv


def model_config(c: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from horovod_tpu.parallel.transformer import (LatentAttention,
                                                  TransformerConfig)
    if c["q_lora_rank"] is not None or c["rope_scaling"] is not None \
            or (c["n_group"], c["topk_group"]) != (1, 1) \
            or not c["rope_interleave"]:
        raise ValueError("the family builds q from one projection, RoPE "
                         "without scaling over interleaved pairs and a "
                         "plain top-k")
    tr = c["training"]
    return TransformerConfig(
        vocab=c["vocab_rows_held"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        mlp="swiglu", tied_head=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], layer_pattern=("mla",),
        mla=LatentAttention(c["kv_lora_rank"], c["qk_nope_head_dim"],
                            c["qk_rope_head_dim"], c["v_head_dim"],
                            rope_theta=float(c["rope_theta"])),
        dense_layers=min(c["first_k_dense_replace"], c["num_hidden_layers"]),
        dense_ff=c["intermediate_size"],
        d_ff=c["moe_intermediate_size"], n_experts=c["router_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_renormalize=c["norm_topk_prob"], moe_score=c["scoring_func"],
        moe_select_bias=c["topk_method"] == "noaux_tc",
        moe_scale=c["routed_scaling_factor"],
        experts_held=c["n_routed_experts"], first_expert=c["first_expert"],
        shared_expert_ff=c["moe_intermediate_size"] * c["n_shared_experts"],
        shared_expert_gate=False,
        dtype=kimi._DTYPES[tr["activation_dtype"]],
        attn_backend=tr["attn_backend"],
        unembed_dtype=kimi._DTYPES[tr["unembed_dtype"]], remat=tr["remat"],
        loss_chunk=tr["loss_chunk"])


def reference_sizes(c: dict) -> dict:
    return {"n_heads": c["num_attention_heads"],
            "kv_rank": c["kv_lora_rank"], "d_nope": c["qk_nope_head_dim"],
            "d_rope": c["qk_rope_head_dim"], "d_v": c["v_head_dim"],
            "rope_theta": float(c["rope_theta"]),
            "experts_per_tok": c["num_experts_per_tok"],
            "first_expert": c["first_expert"],
            "scaling": c["routed_scaling_factor"], "eps": c["rms_norm_eps"]}


class Family(kimi.Family):
    """The routing gauges' ``layer`` counts the EXPERT layers from 0 (the
    leading dense layer has no router)."""

    def __init__(self, ctx):
        from horovod_tpu.parallel.mesh import create_hybrid_mesh
        from horovod_tpu.parallel.transformer import make_parallel_train_step
        t, o = ctx.traffic, ctx.config["training"]["optimizer"]
        self.ctx = ctx
        self.mesh = create_hybrid_mesh(devices=ctx.devices,
                                       **t.get("mesh", {"dp": ctx.chips}))
        self.cfg = model_config(ctx.config)
        # As the other MoE families: the peak rate behind a linear warm-up,
        # no balance loss (the configuration gives no coefficient).
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
        with self.ctx.compiling("train_step"):
            self.compiled = self.lower(state, batch).compile()
        counts = kimi.hlo_counts(self.compiled, self.seq_len,
                                 self.cfg.n_heads)
        self.ctx.log(event="compiled_step", **{
            k: v for k, v in counts.items() if not k.startswith("kda_")})
        if jax.devices()[0].platform != "tpu":
            return
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
        """(d) of ``reference_check`` as a function of the first layer's
        (q, k, v) as the system's forward made them and of the key the
        cotangent is drawn from: an argument, so that the program is the
        same for every seed and its compile is cached."""
        def backward(mla_in, key):
            # The module's attribute as the mixer looks it up, so that a
            # builder's wrong block reaches this call too.
            from horovod_tpu.ops import pallas_attention
            return kimi._gradient_gaps(
                lambda *a: pallas_attention.flash_attention(
                    *a, causal=True, backend=cfg.attn_backend,
                    fallback=False),
                lambda *a: reference.attention(*a, q_block=256),
                mla_in, key)
        return backward

    def reference_check(self, state, cfg=None) -> bool:
        """Parts (a) to (d) above. ``cfg`` (a builder's tool, never the
        harness's: ``tests/benchmark/mla_moe_controls.py``) checks another
        block than the configuration's against the same reference: PERF.md
        shows wrong ones failing."""
        from horovod_tpu.parallel.moe import record_routing
        from horovod_tpu.parallel.transformer import (
            dense_nll, forward_with_stats, mla_from_interleaved)
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
                    "mla_o": [e["mla_o"] for e in layers],
                    "mla_in": layers[0]["mla_in"],
                    "ids": [e["ids"] for e in routed],
                    "held_load": jnp.stack([e["held_load"] for e in routed]),
                    "absent": jnp.stack([e["absent"] for e in routed])}

        def plain(p, t, l, ids, mla_o):
            # The weights as the published checkpoint holds them: the
            # configuration's layout, whatever block ``cfg`` computes.
            out = reference.forward(
                mla_from_interleaved(p, self.cfg, inverse=True), t, l, sizes,
                routing=ids)
            common = [jnp.mean(jnp.any(
                own[:, :, None] == given[:, None, :], axis=-1))
                for own, given in zip(out["routed"], ids)]
            return {"nll": out["nll"], "overlap": jnp.stack(common),
                    "mla_o_rel": jnp.stack([
                        kimi._rel(a, b) for a, b in zip(mla_o,
                                                        out["mla_o"])])}

        system = jax.jit(system)
        if cfg is self.cfg:
            self._system = system
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.ctx.seed % (2 ** 31)), 1)
        with self.ctx.compiling("reference_check"):
            got = system(params, tokens, labels)
            want = jax.device_get(jax.jit(plain)(
                params, tokens, labels, got["ids"], got["mla_o"]))
            grads = jax.device_get(jax.jit(self.backward_gaps(cfg))(
                got.pop("mla_in"), key))
        got = jax.device_get({k: got[k] for k in ("nll", "held_load",
                                                  "absent")})
        for li in range(len(got["held_load"])):
            record_routing(li, got["held_load"][li], got["absent"][li])

        attend = dict(zip(("q", "k", "v"), map(float, grads)))
        token_err = float(np.mean(np.abs(got["nll"] - want["nll"])))
        loss_err = float(abs(got["nll"].mean() - want["nll"].mean()))
        ok = bool(np.all(np.isfinite(got["nll"]))
                  and token_err <= TOL_MEAN_ABS_TOKEN
                  and loss_err <= TOL_MEAN_LOSS
                  and float(want["mla_o_rel"].max()) <= TOL_MLA_O_REL
                  and float(want["overlap"].min()) >= MIN_ROUTING_OVERLAP
                  # (each by itself: a NaN is under no limit)
                  and all(x <= TOL_ATTEND_GRAD_REL for x in attend.values()))
        self.ctx.log(
            event="reference_check", ok=ok,
            system_loss=float(got["nll"].mean()),
            reference_loss=float(want["nll"].mean()),
            mean_abs_token_err=token_err, tol_mean_abs_token=TOL_MEAN_ABS_TOKEN,
            max_abs_token_err=float(np.max(np.abs(got["nll"] - want["nll"]))),
            mean_loss_err=loss_err, tol_mean_loss=TOL_MEAN_LOSS,
            mla_o_rel_err=[float(x) for x in want["mla_o_rel"]],
            tol_mla_o_rel=TOL_MLA_O_REL,
            routing_overlap=[float(x) for x in want["overlap"]],
            min_routing_overlap=MIN_ROUTING_OVERLAP,
            attend_grad_rel_err=attend,
            tol_attend_grad_rel=TOL_ATTEND_GRAD_REL,
            held_load=[[int(v) for v in row] for row in got["held_load"]],
            absent_assignments=[int(v) for v in got["absent"]])
        return ok


def build(ctx) -> Family:
    family = Family(ctx)
    after_window.HOOKS.append(family.stamp_routing)
    return family
