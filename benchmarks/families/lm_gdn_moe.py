"""Family ``lm_gdn_moe``: a decoder of layers of two kinds in a fixed period
(Gated DeltaNet linear attention, and softmax attention with an output gate
and partial RoPE), each ending in a top-k mixture of gated experts of which
this chip holds a share plus one shared expert, through
``make_parallel_train_step`` — the same step builder, optimizer and donation
as families ``lm`` and ``lm_moe_dsa``, whose driver interface (pool, step,
routing gauges after the window) this one inherits. The configuration's
file names the sizes with the source's (Hugging Face) keys, its ``reads``
group says which key counts what is held here, and its ``training`` group
holds what the source does not say.
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
from reference import lm_gdn_moe as reference

RATE_METRIC = "tokens_per_s_per_chip"
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# `correct`: the system's training forward (bf16 activations, the chunked
# rule with its Pallas kernels, flash kernels, bf16 unembedding, float32
# accumulation) against the float32 reference (the recurrence token by
# token) RUN ON THE SYSTEM'S OWN ROUTING SETS, on 2 seeded sequences at the
# timed length: (a) per-token NLL, mean |difference| and difference of the
# means; (b) per Gated DeltaNet layer, the rule's output o: mean over rows
# and heads of |difference| over the mean of |reference|; (c) the system's
# routing sets against the reference's own (top-10 of 512 from its float32
# logits): the share of each token's reference set that the system also
# chose, mean over tokens, in the worst layer.
#
# Measured on the chip (my chip runs, PR 32; PERF.md section 6), a score of
# seeds, the configuration as it stands: mean |NLL difference| 0.00779-
# 0.00797, mean losses at most 1.5e-4 apart, o off by 0.0060-0.0063 /
# 0.0091-0.0095 / 0.0120-0.0125 of its mean size in the three DeltaNet layers
# (bf16's rounding, carried from layer to layer), 98.70-98.85% of each
# token's routing set in common in the worst layer. A wrong block or a lower
# precision, on the same weights (NLL / o in the worst layer / sets in
# common): q and k not L2-normalised: not finite (the state grows without
# bound over 8192 rows); the decay exp(g) left out 0.73 / 14.6 / 22.5%;
# every norm's output through float8_e4m3 (the nearest precision below
# bf16) 0.0464 / 0.073 / 93.5%, mean losses 2.0e-4 apart. Each limit lies
# between its two readings with room on both sides: the NLL's 2.0 times
# over the first and 2.9 under fp8's; o's 2.4 times over the first and 2.4
# under fp8's; the sets' 2.2 points under the first and 3.0 over fp8's. The
# mean loss barely moves for a lower precision (random labels), so it keeps
# the other LM cells' limit, 13 times over the first reading.
TOL_MEAN_ABS_TOKEN = 0.016     # mean |NLL difference| per token
TOL_MEAN_LOSS = 0.002          # |difference of the mean NLLs|
TOL_GDN_O_REL = 0.03           # per DeltaNet layer, mean |do| / mean |o|
MIN_ROUTING_OVERLAP = 0.965    # mean share of a token's set in common


def hlo_counts(compiled) -> dict:
    """Pallas kernels, by all and by the rule's names, and all-reduces in a
    compiled program's text."""
    hlo = compiled.as_text()
    return {"tpu_custom_call": hlo.count("tpu_custom_call"),
            "gdn_fwd_kernels": len(re.findall(r"%gdn_fwd[\w.]* = ", hlo)),
            "gdn_bwd_kernels": len(re.findall(r"%gdn_bwd[\w.]* = ", hlo)),
            "all-reduce": hlo.count("all-reduce(")
            + hlo.count("all-reduce-start(")}


def model_config(c: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from horovod_tpu.parallel.transformer import (GatedDeltaNet,
                                                  TransformerConfig)
    tr = c["training"]
    return TransformerConfig(
        vocab=c["vocab_rows_held"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        n_layers=c["num_hidden_layers"], qk_norm=True, norm_offset=True,
        rope_theta=float(c["rope_theta"]),
        rope_fraction=c["partial_rotary_factor"], attn_gate=True,
        mlp="swiglu", tied_head=c["tie_word_embeddings"],
        layer_pattern=("gdn",) * (c["full_attention_interval"] - 1)
        + ("attn",),
        gdn=GatedDeltaNet(
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            conv_width=c["linear_conv_kernel_dim"], chunk=tr["gdn_chunk"],
            backend=tr["gdn_backend"]),
        d_ff=c["moe_intermediate_size"], n_experts=c["router_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_renormalize=c["norm_topk_prob"], experts_held=c["num_experts"],
        first_expert=c["first_expert"],
        shared_expert_ff=c["shared_expert_intermediate_size"],
        dtype=_DTYPES[tr["activation_dtype"]],
        attn_backend=tr["attn_backend"],
        unembed_dtype=_DTYPES[tr["unembed_dtype"]], remat=tr["remat"],
        loss_chunk=tr["loss_chunk"])


def reference_sizes(c: dict) -> dict:
    return {"n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
            "rotary_dim": int(c["head_dim"] * c["partial_rotary_factor"]),
            "rope_theta": float(c["rope_theta"]),
            "full_interval": c["full_attention_interval"],
            "gdn_k_heads": c["linear_num_key_heads"],
            "gdn_v_heads": c["linear_num_value_heads"],
            "gdn_dk": c["linear_key_head_dim"],
            "gdn_dv": c["linear_value_head_dim"],
            "experts_per_tok": c["num_experts_per_tok"],
            "first_expert": c["first_expert"]}


class Family(lm_moe_dsa.Family):
    """Family ``lm_moe_dsa``'s pool, step and routing gauges around another
    model and another check."""

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
        counts = hlo_counts(self.compiled)
        self.ctx.log(event="compiled_step",
                     gdn_backend=resolve_backend(self.cfg.gdn.backend),
                     **counts)
        if jax.devices()[0].platform != "tpu":
            return
        if resolve_backend(self.cfg.gdn.backend) == "pallas" and not (
                counts["gdn_fwd_kernels"] and counts["gdn_bwd_kernels"]):
            raise RuntimeError("no gdn_fwd / gdn_bwd kernel in the compiled "
                               "step: the rule's kernels are not in it")
        if self.cfg.attn_backend == "pallas" \
                and not counts["tpu_custom_call"]:
            raise RuntimeError("no tpu_custom_call in the compiled step: "
                               "the flash kernel is not in it")

    # -- correctness --------------------------------------------------------

    def reference_check(self, state, cfg=None) -> bool:
        """Parts (a) to (c) above. ``cfg`` (a builder's tool, never the
        harness's) checks another block than the configuration's against
        the same reference: PERF.md shows wrong ones failing."""
        from horovod_tpu.parallel.moe import record_routing
        from horovod_tpu.parallel.transformer import (dense_nll,
                                                      forward_with_stats)
        cfg = self.cfg if cfg is None else cfg
        params = state[0]
        rng = np.random.default_rng(self.ctx.seed + 1)
        n = self.ctx.traffic.get("reference_sequences", 2)
        tok = rng.integers(0, cfg.vocab, size=(n, self.seq_len + 1),
                           dtype=np.int32)
        tokens, labels = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])
        sizes = reference_sizes(self.ctx.config)

        def system(p, t, l):
            logits, layers = forward_with_stats(p, t, cfg, self.mesh)
            return {"nll": dense_nll(logits, l),
                    "gdn_o": [e["gdn_o"] for e in layers if "gdn_o" in e],
                    "ids": [e["ids"] for e in layers],
                    "held_load": jnp.stack([e["held_load"] for e in layers]),
                    "absent": jnp.stack([e["absent"] for e in layers])}

        def plain(p, t, l, ids, gdn_o):
            out = reference.forward(p, t, l, sizes, routing=ids)
            common = [jnp.mean(jnp.any(
                own[:, :, None] == given[:, None, :], axis=-1))
                for own, given in zip(out["routed"], ids)]
            o_err = [jnp.mean(jnp.abs(got.astype(jnp.float32) - want))
                     / jnp.mean(jnp.abs(want))
                     for got, want in zip(gdn_o, out["gdn_o"])]
            return {"nll": out["nll"], "overlap": jnp.stack(common),
                    "gdn_o_rel": jnp.stack(o_err)}

        system = jax.jit(system)
        if cfg is self.cfg:
            self._system = system
        with self.ctx.compiling("reference_check"):
            got = system(params, tokens, labels)
            want = jax.device_get(jax.jit(plain)(
                params, tokens, labels, got["ids"], got["gdn_o"]))
        got = jax.device_get({k: got[k] for k in ("nll", "held_load",
                                                  "absent")})
        for li in range(len(got["held_load"])):
            record_routing(li, got["held_load"][li], got["absent"][li])

        token_err = float(np.mean(np.abs(got["nll"] - want["nll"])))
        loss_err = float(abs(got["nll"].mean() - want["nll"].mean()))
        ok = bool(np.all(np.isfinite(got["nll"]))
                  and token_err <= TOL_MEAN_ABS_TOKEN
                  and loss_err <= TOL_MEAN_LOSS
                  and float(want["gdn_o_rel"].max()) <= TOL_GDN_O_REL
                  and float(want["overlap"].min()) >= MIN_ROUTING_OVERLAP)
        self.ctx.log(
            event="reference_check", ok=ok,
            system_loss=float(got["nll"].mean()),
            reference_loss=float(want["nll"].mean()),
            mean_abs_token_err=token_err, tol_mean_abs_token=TOL_MEAN_ABS_TOKEN,
            max_abs_token_err=float(np.max(np.abs(got["nll"] - want["nll"]))),
            mean_loss_err=loss_err, tol_mean_loss=TOL_MEAN_LOSS,
            gdn_o_rel_err=[float(x) for x in want["gdn_o_rel"]],
            tol_gdn_o_rel=TOL_GDN_O_REL,
            routing_overlap=[float(x) for x in want["overlap"]],
            min_routing_overlap=MIN_ROUTING_OVERLAP,
            held_load=[[int(v) for v in row] for row in got["held_load"]],
            absent_assignments=[int(v) for v in got["absent"]])
        return ok


def build(ctx) -> Family:
    family = Family(ctx)
    after_window.HOOKS.append(family.stamp_routing)
    return family
