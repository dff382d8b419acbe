"""Pipelined transformer LM: dp × pp × tp composed over one mesh.

Completes the parallelism matrix (the sibling `transformer.py` composes
dp × sp × tp × ep): transformer layers are partitioned into `pp` stages
driven by the 1F1B-style memory-bounded schedule
(:func:`horovod_tpu.parallel.pipeline.one_f_one_b`), with Megatron tensor
parallelism inside each stage and data parallelism over the batch. One
compiled SPMD program: `ppermute` stage handoffs, per-layer tp `psum`s and
the dp gradient `pmean` all ride ICI under XLA's scheduler.

Embedding and the loss head (final RMS norm + tied unembed) live OUTSIDE
the pipeline so every stage runs the same uniform block structure (the
lockstep-SPMD requirement): the embedding's gradient is assembled from the
head's unembed contribution (last pp rank) plus the input-side cotangents
(pp rank 0) that `one_f_one_b` returns — summed with one `psum` over pp.

Gradient sync is the unified spec-grouped collective plan (ISSUE 20): the
step interprets the same `GradSync`/`ZeroPlan` data every other plane does
(`DistributedOptimizer(mesh=, param_specs=)` → `plan_grad_sync` →
`fused_allreduce(reduce_axes=)`), with `pp` excluded from every allreduce
reduce set — each stage owns its weights. The per-leaf
`grad_sync_by_spec` walk this file used to run stays exported from
`parallel.mesh` as the empirical reference the plan's denominators are
pinned against in tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas_attention import flash_attention
# Re-exported reference (not called in the step body): the per-leaf
# empirical sync rule the fused GradSync plan is parity-pinned against.
from .mesh import grad_sync_by_spec  # noqa: F401
from .pipeline import one_f_one_b
from .transformer import (TransformerConfig, _check_dense, _rms_norm,
                          dense_nll)


def _axes(mesh: Mesh):
    return set(mesh.axis_names)


def init_pp_params(rng, cfg: TransformerConfig, n_stages: int):
    """Parameters in the pipeline layout: per-layer weights stacked as
    [n_stages, layers_per_stage, ...]; embed/lnf replicated (the head)."""
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} must divide into "
                         f"pp={n_stages} stages")
    lps = cfg.n_layers // n_stages
    k = jax.random.split(rng, 6)
    d, f = cfg.d_model, cfg.d_ff

    def norm(key, shape, s):
        return jax.random.normal(key, shape) * s

    return {
        "embed": norm(k[0], (cfg.vocab, d), 0.02),
        "lnf": jnp.ones((d,)),
        "stages": {
            "ln1": jnp.ones((n_stages, lps, d)),
            "wqkv": norm(k[1], (n_stages, lps, d, 3 * d), d ** -0.5),
            "wo": norm(k[2], (n_stages, lps, d, d), d ** -0.5),
            "ln2": jnp.ones((n_stages, lps, d)),
            "w1": norm(k[3], (n_stages, lps, d, f), d ** -0.5),
            "w2": norm(k[4], (n_stages, lps, f, d), f ** -0.5),
        },
    }


def pp_param_specs(mesh: Mesh) -> dict:
    """PartitionSpec tree for :func:`init_pp_params`: stage dim over pp,
    Megatron column/row sharding over tp, head replicated."""
    tp = "tp" if "tp" in _axes(mesh) else None
    return {
        "embed": P(),
        "lnf": P(),
        "stages": {
            "ln1": P("pp", None, None),
            "wqkv": P("pp", None, None, tp),   # column: heads over tp
            "wo": P("pp", None, tp, None),     # row: one psum recombines
            "ln2": P("pp", None, None),
            "w1": P("pp", None, None, tp),
            "w2": P("pp", None, tp, None),
        },
    }


def make_pp_transformer_train_step(cfg: TransformerConfig, mesh: Mesh,
                                   optimizer: optax.GradientTransformation,
                                   n_microbatches: int,
                                   *,
                                   zero: bool = False,
                                   wire_dtype=None,
                                   overlap=None,
                                   guard_nonfinite=None,
                                   fusion_threshold=None):
    """Build ``(init_state, step)`` for the pipelined transformer.

    ``step(params, opt_state, tokens, labels)`` runs one 1F1B update and
    returns ``(params, opt_state, loss)``; tokens/labels are global
    [B, T] int32 sharded over dp, with B divisible by
    dp_size * n_microbatches.

    Gradient sync interprets the unified spec-grouped collective plan:
    leaves fuse only within their reduce-axis group
    (:func:`~horovod_tpu.ops.fusion.plan_grad_sync` keyed by
    :func:`pp_param_specs`, ``pp`` excluded — each stage owns its
    weights), so on a (dp, pp, tp) mesh the default plan carries TWO
    bucket collectives (replicated head/norm leaves psum over (dp, tp);
    tp-sharded matrices over dp with the psum-transpose correction in the
    bucket prescale) instead of one per leaf. Same composition matrix as
    the core stack:

    * ``zero=True`` — ZeRO-1 over dp: the spec-grouped ``ZeroPlan`` with
      pp riding as a real shard axis of the stacked state (stage leaves
      shard over (pp, tp); the head leaves take the full (dp, pp, tp)
      reduce, numerically equal to the pp-skip mean because the step's
      explicit pp psum already made them pp-identical).
    * ``wire_dtype=`` — bf16/fp8 bucket wire, fp32 scales + accumulation.
    * ``overlap=`` — barrier-chained per-bucket emission; the 1F1B scan
      hides backward-completion order from the probe, so emission runs in
      plan order (reorder-free, still unmergeable by XLA's combiner).
    * ``guard_nonfinite=`` (default ``HVD_GUARD_NONFINITE``) — skip-step
      guard; the allreduce plan never reduces over pp, so the verdict is
      folded with ONE scalar pmin over pp — the only collective the guard
      adds here (the ZeRO plan's flags already fold over its nonscatter
      axes).
    * accum — native: ``n_microbatches`` IS the accumulation shape (1F1B
      sums M microbatch gradients before the one exchange); there is no
      separate accum_steps knob to double-divide with.
    """
    _check_dense(cfg, "make_pp_transformer_train_step")
    from ..optimizer import DistributedOptimizer
    from ..utils import config as _config

    axes = _axes(mesh)
    if "pp" not in axes:
        raise ValueError("mesh must have a 'pp' axis")
    S = mesh.shape["pp"]
    tp_size = mesh.shape.get("tp", 1)
    has_tp = "tp" in axes
    if cfg.n_heads % tp_size:
        raise ValueError(f"n_heads={cfg.n_heads} must divide tp={tp_size}")
    n_heads_local = cfg.n_heads // tp_size
    d_head = cfg.d_model // cfg.n_heads
    M = n_microbatches
    specs = pp_param_specs(mesh)
    batch_spec = P("dp" if "dp" in axes else None, None)
    if guard_nonfinite is None:
        guard_nonfinite = _config.guard_nonfinite()
    # The allreduce plan skips pp (stage weights are never replicated
    # across it); the ZeRO plan instead carries pp as a shard axis — the
    # stacked [dp, ns·shard_len] state layout must tile over every mesh
    # axis the stage weights are actually split across.
    dist_opt = DistributedOptimizer(
        optimizer, zero=zero, wire_dtype=wire_dtype, overlap=overlap,
        fusion_threshold=fusion_threshold, mesh=mesh, param_specs=specs,
        skip_axes=() if zero else ("pp",))

    def _block(layer_i, stage_leaves, x):
        """One transformer block (pre-norm attention + FFN) from the
        stage's stacked leaves; tp column/row sharding inside."""
        g = lambda name: stage_leaves[name][0, layer_i]  # noqa: E731
        h = _rms_norm(x, g("ln1"))
        qkv = h @ g("wqkv").astype(cfg.dtype)
        B, T, _ = qkv.shape
        # HEAD-major column layout [D, H, 3, dh]: a tp column-slice then
        # holds whole heads (each with its own q,k,v), so the sharded
        # model computes the SAME function as tp=1 from the same weights
        # (checkpoints stay portable across mesh shapes).
        qkv = qkv.reshape(B, T, n_heads_local, 3, d_head)
        attn = flash_attention(qkv[..., 0, :], qkv[..., 1, :],
                               qkv[..., 2, :], causal=True,
                               backend=cfg.attn_backend).astype(cfg.dtype)
        proj = attn.reshape(B, T, n_heads_local * d_head) \
            @ g("wo").astype(cfg.dtype)
        if has_tp:
            proj = lax.psum(proj, "tp")
        x = x + proj
        h = _rms_norm(x, g("ln2"))
        up = jax.nn.gelu(h @ g("w1").astype(cfg.dtype))
        down = up @ g("w2").astype(cfg.dtype)
        if has_tp:
            down = lax.psum(down, "tp")
        return x + down

    lps = cfg.n_layers // S

    def stage_fn(stage_leaves, act):
        for i in range(lps):
            act = _block(i, stage_leaves, act)
        return act

    def head_loss(act, labels, head):
        h = _rms_norm(act, head["lnf"])
        logits = jnp.matmul(h.astype(cfg.unembed_dtype),
                            head["embed"].T.astype(cfg.unembed_dtype),
                            preferred_element_type=jnp.float32)
        return jnp.mean(dense_nll(logits, labels))

    def _step(params, opt_state, tokens, labels):
        B, T = tokens.shape
        mb = B // M
        tok_m = tokens.reshape(M, mb, T)
        y_m = labels.reshape(M, mb, T)
        head = {"embed": params["embed"], "lnf": params["lnf"]}

        # Tokens (not embeddings) ride the microbatch buffer: inject_fn
        # embeds per microbatch at stage-0 injection, and the input
        # cotangents stream straight into a [vocab, D] scatter-add — no
        # O(M) activation-sized buffer exists, preserving the schedule's
        # O(S) memory bound end to end.
        def inject(toks):
            return params["embed"][toks].astype(cfg.dtype)

        def accumulate_embed_grad(acc, bi, din):
            return acc.at[tok_m[bi].reshape(-1)].add(
                din.astype(acc.dtype).reshape(-1, cfg.d_model))

        loss, sg, hg, d_embed_in = one_f_one_b(
            stage_fn, params["stages"], tok_m, y_m, head_loss,
            axis_name="pp", head_params=head, inject_fn=inject,
            input_grad_acc=(jnp.zeros_like(params["embed"]),
                            accumulate_embed_grad))

        # Embedding gradient = head (unembed) contribution on the last pp
        # rank + input-lookup contribution on pp rank 0, merged by ONE
        # psum over pp (zeros elsewhere). lnf rides the same psum.
        hg = jax.tree_util.tree_map(lambda g: lax.psum(g, "pp"), hg)
        d_embed = hg["embed"] + lax.psum(d_embed_in, "pp")

        grads = {"embed": d_embed, "lnf": hg["lnf"], "stages": sg}

        # One plan, every plane: the spec-grouped GradSync/ZeroPlan
        # interpretation replaces the old per-leaf grad_sync_by_spec walk
        # — same denominators (parity-pinned against it in tests), fused
        # buckets, one collective per spec group.
        finite_out = {} if guard_nonfinite else None
        upd_kw = {} if finite_out is None else {"finite_out": finite_out}
        updates, new_opt_state = dist_opt.update(
            grads, opt_state, params, **upd_kw)
        new_params = optax.apply_updates(params, updates)
        if finite_out is not None:
            all_finite = finite_out["all_finite"]
            if not zero:
                # The allreduce plan never reduces over pp, so per-stage
                # verdicts must fold once for a mesh-wide skip decision
                # (divergent decisions would corrupt the pp-replicated
                # head leaves).
                all_finite = lax.pmin(
                    all_finite.astype(jnp.int32), "pp") > 0

            def _keep(new, old):
                return jnp.where(all_finite, new, old)
            new_params = jax.tree_util.tree_map(_keep, new_params, params)
            new_opt_state = jax.tree_util.tree_map(
                _keep, new_opt_state, opt_state)
            loss = jnp.where(all_finite, loss, jnp.zeros_like(loss))
        params, opt_state = new_params, new_opt_state
        loss = lax.pmean(loss, tuple(a for a in axes if a != "pp"))
        return params, opt_state, loss

    def _opt_specs(opt_state):
        # Derivable from any opt_state with the right STRUCTURE, so the
        # checkpoint-restore path (params/opt_state from disk, init_state
        # never called) works too; handles both the mirrored replicated
        # state and the ZeRO stacked-shard layout.
        from .. import training
        return training._hybrid_opt_specs(dist_opt, opt_state, specs)

    def init_state(rng):
        params = init_pp_params(rng, cfg, S)
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
        # dist_opt.init commits the state to the mesh itself (param specs
        # mirrored leaf-for-leaf; ZeRO stacks + dp-shards per the plan).
        return params, dist_opt.init(params)

    fn_box = {}

    def _jitted(opt_state):
        if "fn" not in fn_box:
            ospecs = _opt_specs(opt_state)
            fn_box["fn"] = jax.jit(jax.shard_map(
                _step, mesh=mesh,
                in_specs=(specs, ospecs, batch_spec, batch_spec),
                out_specs=(specs, ospecs, P()),
                check_vma=False))
        return fn_box["fn"]

    def step(params, opt_state, tokens, labels):
        return _jitted(opt_state)(params, opt_state, tokens, labels)

    # AOT handle (jax .lower convention) for HLO-pinned tests.
    step.lower = lambda params, opt_state, tokens, labels: _jitted(
        opt_state).lower(params, opt_state, tokens, labels)

    return init_state, step
