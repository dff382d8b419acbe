"""Family ``lm_swa_moe``: the afmoe block (``model_type`` ``afmoe``, Arcee's
Trinity) — grouped-query attention over a sliding window with RoPE on three
layers in four and over the whole causal past without positions on the
fourth, each behind a sigmoid output gate, sandwich norms, the embedding
scaled by sqrt(d), a leading dense feed-forward and after it a top-k
mixture of gated experts under sigmoid scores of which this chip holds a
share, beside an ungated shared expert — through
``make_parallel_train_step``. Family ``lm_mla_moe``'s ``Family`` (pool,
placement, the step and its compile-time counts, routing gauges after the
window) and the Kimi family's check helpers, around another model and
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
from families import lm_mla_moe as mla
from lib import after_window
from reference import lm_swa_moe as reference

RATE_METRIC = "tokens_per_s_per_chip"
_KINDS = {"sliding_attention": "swa", "full_attention": "attn"}

# `correct`: the system's training forward (bf16 activations, the windowed
# and the causal flash kernels, bf16 unembedding, float32 accumulation)
# against the float32 reference (blocked float32 softmax attention over
# each query block's band, on the weights' published layout: the gate a
# projection of its own) RUN ON THE SYSTEM'S OWN ROUTING SETS, on the
# pool's 2 seeded sequences at the timed length: (a) per-token NLL, mean
# |difference| and difference of the means; (b) per layer, the attention's
# output before the gate: mean over rows and heads of |difference| over
# the mean of |reference|; (c) the system's routing sets against the
# reference's own (top-8 of 128 from its float32 scores): the share of each
# token's reference set that the system also chose, mean over tokens, in
# the worst layer. And the BACKWARD the step runs, which no forward shows:
# (d) the first window layer's attention call and the full layer's, from
# the q, k, v the system's forward gave them (the first sequence), each
# under a cotangent drawn from the seed: the kernels' gradients against
# ``jax.vjp`` of the float32 blocked attention, mean |difference| over
# mean |reference| a gradient.
#
# Measured on a TPU v5e: PERF.md section 6, the Trinity-Mini entry, where
# the readings of the block as it stands and of every control
# (``tests/benchmark/swa_moe_controls.py``) are beside these limits.
#
# Each limit lies between the block's largest reading over seven seeds and
# the reading with every norm's output (the projections' and experts'
# inputs) rounded to float8, the precision below bf16; the gradients'
# between the block's and float8 attention operands'.
TOL_MEAN_ABS_TOKEN = 0.012     # mean |NLL difference| per token: 0.0092
#                                as it stands, 0.065 with float8
TOL_MEAN_LOSS = 0.00025        # |difference of the mean NLLs|: 0.000092,
#                                0.00038
TOL_ATTN_O_REL = 0.016         # per layer, mean |do| / mean |o|: 0.0092,
#                                0.061
MIN_ROUTING_OVERLAP = 0.975    # mean share of a token's set in common:
#                                0.989, 0.930
TOL_ATTEND_GRAD_REL = 0.009    # each attention call's dq, dk, dv: 0.0033,
#                                0.059 (float8 operands)


def layer_kinds(c: dict):
    """The program's kind of each layer held (``layers_held`` in the
    published ``layer_types``) and the number of leading dense ones."""
    held = c["layers_held"]
    if len(held) != c["num_hidden_layers"] \
            or held != list(range(held[0], held[0] + len(held))):
        raise ValueError("layers_held names num_hidden_layers consecutive "
                         "published layers")
    dense = [i < c["num_dense_layers"] for i in held]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers held lead the others")
    return tuple(_KINDS[c["layer_types"][i]] for i in held), sum(dense)


def model_config(c: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from horovod_tpu.parallel.transformer import (SlidingWindow,
                                                  TransformerConfig)
    if c["rope_scaling"] is not None or (
            c["n_group"], c["topk_group"], c["num_expert_groups"],
            c["num_limited_groups"]) != (1, 1, 1, 1):
        raise ValueError("the family builds RoPE without scaling and a "
                         "plain top-k")
    tr = c["training"]
    kinds, n_dense = layer_kinds(c)
    return TransformerConfig(
        vocab=c["vocab_rows_held"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        n_layers=c["num_hidden_layers"], qk_norm=True, mlp="swiglu",
        tied_head=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        layer_pattern=kinds,
        swa=SlidingWindow(c["sliding_window"], float(c["rope_theta"])),
        attn_gate=True, post_norms=True,
        embed_scale=c["hidden_size"] ** 0.5 if c["mup_enabled"] else 1.0,
        dense_layers=n_dense, dense_ff=c["intermediate_size"],
        d_ff=c["moe_intermediate_size"], n_experts=c["router_experts"],
        moe_top_k=c["num_experts_per_tok"], moe_renormalize=c["route_norm"],
        moe_score=c["score_func"], moe_select_bias=True,
        moe_scale=c["route_scale"], experts_held=c["num_experts"],
        first_expert=c["first_expert"],
        shared_expert_ff=c["moe_intermediate_size"]
        * c["num_shared_experts"], shared_expert_gate=False,
        dtype=kimi._DTYPES[tr["activation_dtype"]],
        attn_backend=tr["attn_backend"],
        unembed_dtype=kimi._DTYPES[tr["unembed_dtype"]], remat=tr["remat"],
        loss_chunk=tr["loss_chunk"])


def reference_sizes(c: dict) -> dict:
    return {"n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
            "window": c["sliding_window"],
            "rope_theta": float(c["rope_theta"]),
            "kinds": ["window" if k == "swa" else "full"
                      for k in layer_kinds(c)[0]],
            "embed_scale": c["hidden_size"] ** 0.5 if c["mup_enabled"]
            else 1.0,
            "experts_per_tok": c["num_experts_per_tok"],
            "first_expert": c["first_expert"], "scaling": c["route_scale"],
            "eps": c["rms_norm_eps"]}


def init_family(family, ctx, cfg) -> None:
    """What an expert-layer family's ``__init__`` does, for the model
    ``cfg`` (a ``TransformerConfig``): the mesh the traffic asks for, the
    donated step of ``make_parallel_train_step`` under AdamW at the
    configuration's peak rate behind a linear warm-up, no balance loss (no
    configuration gives a coefficient), and the batch's shape. A later
    family passes its own ``cfg`` here."""
    from horovod_tpu.parallel.mesh import create_hybrid_mesh
    from horovod_tpu.parallel.transformer import make_parallel_train_step
    t, o = ctx.traffic, ctx.config["training"]["optimizer"]
    family.ctx, family.cfg = ctx, cfg
    family.mesh = create_hybrid_mesh(devices=ctx.devices,
                                     **t.get("mesh", {"dp": ctx.chips}))
    family.init_state, family._step = make_parallel_train_step(
        cfg, family.mesh, optax.adamw(
            optax.linear_schedule(0.0, o["lr"], o["warmup_steps"]),
            b1=o["b1"], b2=o["b2"], weight_decay=o["weight_decay"]),
        aux_weight=0.0)
    family.batch = t["batch_per_chip"] * ctx.chips
    family.seq_len = t["seq_len"]
    family.units_per_step = family.batch * family.seq_len
    family.batch_sharding = NamedSharding(family.mesh, P("dp", None))
    family.compiled = None
    family._pool, family._params, family._system = [], None, None


class Family(mla.Family):
    """The routing gauges' ``layer`` counts the EXPERT layers from 0 (the
    leading dense layer has no router)."""

    def __init__(self, ctx):
        init_family(self, ctx, model_config(ctx.config))
        gain = ctx.config["training"]["post_mixer_norm_init"]
        made = self.init_state

        def init_state(key):
            # The post-mixer norms' weights start at ``gain``, not one (the
            # configuration's ``departs`` says why).
            params, opt_state = made(key)
            layers = [dict(layer, post_ln1=layer["post_ln1"] * gain)
                      for layer in params["layers"]]
            return dict(params, layers=layers), opt_state
        self.init_state = init_state

    # -- correctness --------------------------------------------------------

    def backward_gaps(self, cfg):
        """(d) of ``reference_check`` as a function of the first window
        layer's and the full layer's (q, k, v) as the system's forward
        made them and of the key the cotangents are drawn from (an
        argument: the program is the same for every seed)."""
        def backward(swa_in, full_in, key):
            # The module's attribute as the mixer looks it up, so that a
            # control's wrong block reaches these calls too.
            from horovod_tpu.ops import pallas_attention

            def flash(window):
                return lambda *a: pallas_attention.flash_attention(
                    *a, causal=True, backend=cfg.attn_backend,
                    fallback=False, window=window)
            keys = jax.random.split(key)
            # The block's window on the system's side, the configuration's
            # on the reference's.
            return {
                "swa": kimi._gradient_gaps(
                    flash(cfg.swa.window), lambda *a: reference.attention(
                        *a, self.cfg.swa.window, q_block=256), swa_in,
                    keys[0]),
                "full": kimi._gradient_gaps(
                    flash(None), lambda *a: reference.attention(
                        *a, q_block=256), full_in, keys[1])}
        return backward

    def reference_check(self, state, cfg=None) -> bool:
        """Parts (a) to (d) above. ``cfg`` (a control's tool, never the
        harness's: ``tests/benchmark/swa_moe_controls.py``) checks another
        block than the configuration's against the same reference: PERF.md
        shows wrong ones failing."""
        from horovod_tpu.parallel.moe import record_routing
        from horovod_tpu.parallel.transformer import (
            dense_nll, forward_with_stats, gate_from_projection, layer_kind)
        cfg = self.cfg if cfg is None else cfg
        params = state[0]
        rng = np.random.default_rng(self.ctx.seed + 1)
        n = self.ctx.traffic.get("reference_sequences", 2)
        tok = rng.integers(0, cfg.vocab, size=(n, self.seq_len + 1),
                           dtype=np.int32)
        tokens, labels = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])
        sizes = reference_sizes(self.ctx.config)
        kinds = [layer_kind(self.cfg, i) for i in range(self.cfg.n_layers)]

        def system(p, t, l):
            logits, layers = forward_with_stats(p, t, cfg, self.mesh)
            routed = [e for e in layers if "ids" in e]

            def first(kind):
                e = layers[kinds.index(kind)]["attn_in"]
                return tuple(x[:1] for x in e)
            return {"nll": dense_nll(logits, l),
                    "attn_o": [e["attn_o"] for e in layers],
                    "swa_in": first("swa"), "full_in": first("attn"),
                    "ids": [e["ids"] for e in routed],
                    "held_load": jnp.stack([e["held_load"] for e in routed]),
                    "absent": jnp.stack([e["absent"] for e in routed])}

        def plain(p, t, l, ids, attn_o):
            # The weights as the published checkpoint holds them: the
            # configuration's layout, whatever block ``cfg`` computes.
            out = reference.forward(
                gate_from_projection(p, self.cfg, inverse=True), t, l,
                sizes, routing=ids)
            common = [jnp.mean(jnp.any(
                own[:, :, None] == given[:, None, :], axis=-1))
                for own, given in zip(out["routed"], ids)]
            return {"nll": out["nll"], "overlap": jnp.stack(common),
                    "attn_o_rel": jnp.stack([
                        kimi._rel(a, b) for a, b in zip(attn_o,
                                                        out["attn_o"])])}

        system = jax.jit(system)
        if cfg is self.cfg:
            self._system = system
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.ctx.seed % (2 ** 31)), 1)
        with self.ctx.compiling("reference_check"):
            got = system(params, tokens, labels)
            want = jax.device_get(jax.jit(plain)(
                params, tokens, labels, got["ids"], got["attn_o"]))
            grads = jax.device_get(jax.jit(self.backward_gaps(cfg))(
                got.pop("swa_in"), got.pop("full_in"), key))
        got = jax.device_get({k: got[k] for k in ("nll", "held_load",
                                                  "absent")})
        for li in range(len(got["held_load"])):
            record_routing(li, got["held_load"][li], got["absent"][li])

        attend = {kind: dict(zip(("q", "k", "v"), map(float, gaps)))
                  for kind, gaps in grads.items()}
        token_err = float(np.mean(np.abs(got["nll"] - want["nll"])))
        loss_err = float(abs(got["nll"].mean() - want["nll"].mean()))
        ok = bool(np.all(np.isfinite(got["nll"]))
                  and token_err <= TOL_MEAN_ABS_TOKEN
                  and loss_err <= TOL_MEAN_LOSS
                  and float(want["attn_o_rel"].max()) <= TOL_ATTN_O_REL
                  and float(want["overlap"].min()) >= MIN_ROUTING_OVERLAP
                  # (each by itself: a NaN is under no limit)
                  and all(x <= TOL_ATTEND_GRAD_REL for gaps in
                          attend.values() for x in gaps.values()))
        self.ctx.log(
            event="reference_check", ok=ok,
            system_loss=float(got["nll"].mean()),
            reference_loss=float(want["nll"].mean()),
            mean_abs_token_err=token_err,
            tol_mean_abs_token=TOL_MEAN_ABS_TOKEN,
            max_abs_token_err=float(np.max(np.abs(got["nll"] - want["nll"]))),
            mean_loss_err=loss_err, tol_mean_loss=TOL_MEAN_LOSS,
            attn_o_rel_err=[float(x) for x in want["attn_o_rel"]],
            tol_attn_o_rel=TOL_ATTN_O_REL, layer_kinds=kinds,
            routing_overlap=[float(x) for x in want["overlap"]],
            min_routing_overlap=MIN_ROUTING_OVERLAP,
            attend_grad_rel_err=attend,
            tol_attend_grad_rel=TOL_ATTEND_GRAD_REL,
            held_load=[[int(v) for v in row] for row in got["held_load"]],
            absent_assignments=[int(v) for v in got["absent"]])
        return ok

    # -- counters ---------------------------------------------------------

    def stamp_routing(self) -> None:
        """The base's, over the pool's batches taken ``reference_sequences``
        at a time: the shape the check's forward was compiled for."""
        n = self.ctx.traffic.get("reference_sequences", 2)
        pool = self._pool
        self._pool = [
            tuple(np.concatenate(part) for part in zip(*pool[i:i + n]))
            for i in range(0, len(pool) - n + 1, n)]
        try:
            super().stamp_routing()
        finally:
            self._pool = pool


def build(ctx) -> Family:
    family = Family(ctx)
    after_window.HOOKS.append(family.stamp_routing)
    return family
