"""Parallel transformer LM: dp × tp × sp × ep composed over one mesh.

Net-new TPU capability demonstrating the framework's multi-axis parallelism
(the reference is dp-only, SURVEY §2.4): batch sharded over ``dp``, sequence
over ``sp`` (ring attention), Megatron column/row weight sharding over
``tp``, and optionally a top-1 MoE FFN over ``ep``. The train step is one
compiled SPMD program (``shard_map`` over the mesh) whose collectives —
gradient ``pmean`` over dp/sp, ``psum`` of row-parallel matmuls over tp,
``ppermute`` K/V rings over sp, ``all_to_all`` MoE dispatch over ep — all
ride ICI under XLA's scheduler.

Params are global jax.Arrays placed with `NamedSharding` spec trees
(`param_specs`); tp-sharded weights never exist unsharded on any chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .moe import moe_ffn
from .ring import ring_attention


@dataclasses.dataclass(frozen=True)
class Indexer:
    """The lightning indexer of sparse attention (``ops/sparse_attention``):
    ``n_heads`` index heads of ``d_head`` over ONE index key head; each
    query row attends to the ``topk`` keys of largest index score."""
    n_heads: int
    d_head: int
    topk: int


@dataclasses.dataclass(frozen=True)
class GatedDeltaNet:
    """A Gated DeltaNet linear-attention mixer (``ops/gated_delta.py``):
    ``n_k_heads`` query/key heads of ``d_k``, each serving
    ``n_v_heads // n_k_heads`` consecutive value heads of ``d_v``; a causal
    depthwise convolution of ``conv_width`` over time on q, k and v; the
    rule in chunks of ``chunk`` rows by ``backend`` (``"auto"``: the Pallas
    kernels on a TPU, the XLA scan elsewhere), which also chooses the
    convolution's form (``ops/causal_conv.py``: the kernels
    ``conv_silu_fwd`` / ``conv_silu_bwd`` where its shapes tile)."""
    n_k_heads: int
    n_v_heads: int
    d_k: int
    d_v: int
    conv_width: int = 4
    chunk: int = 64
    backend: str = "auto"


@dataclasses.dataclass(frozen=True)
class KimiDeltaAttention:
    """A Kimi Delta Attention mixer (``ops/gated_delta.py``, the gate per
    channel): ``n_heads`` heads of ``d_head`` for q, k and v alike; a
    causal depthwise convolution of ``conv_width`` on each; the log-decay
    (one value a key channel) and the output gate from low-rank pairs of
    rank ``d_head``; the rule in chunks of ``chunk`` rows (a power of two)
    by ``backend``, which also chooses the convolution's form
    (``ops/causal_conv.py``)."""
    n_heads: int
    d_head: int
    conv_width: int = 4
    chunk: int = 64
    backend: str = "auto"


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention (MLA) over ``cfg.n_heads`` heads: keys
    and values are expanded from ONE normed latent of ``kv_rank`` a token
    (``d_nope`` of a key and ``d_v`` of a value a head); ``d_shared``
    further key columns are one vector a token that every head shares (the
    published ``qk_rope_head_dim``). A query head is ``d_nope + d_shared``
    wide and the softmax scale is that width's. ``rope_theta`` > 0: the
    trailing ``d_shared`` columns of every query head and the shared key
    part are rotated by position (RoPE of base ``rope_theta``, pairs
    (i, i + d_shared/2); a checkpoint that rotates the interleaved pairs
    (2i, 2i + 1) is taken by :func:`mla_from_interleaved`); 0: no
    positions (``mla_use_nope``)."""
    kv_rank: int
    d_nope: int
    d_shared: int
    d_v: int
    rope_theta: float = 0.0


@dataclasses.dataclass(frozen=True)
class SlidingWindow:
    """Sliding-window attention ("swa" layers): the projected attention the
    configuration's fields describe (grouped heads, q/k norm, the output
    gate), each query seeing the ``window`` keys up to and including its
    own (``ops/pallas_attention.flash_attention(window=)``), positions by
    RoPE of base ``rope_theta`` (0: none). The model's "attn" layers keep
    ``TransformerConfig.rope_theta``: 0 there makes them full attention
    without positions beside the windowed ones."""
    window: int
    rope_theta: float = 0.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 1024
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512             # feed-forward width (of one expert, if any)
    n_experts: int = 0          # 0 = dense MLP; >0 = experts the router sees
    # -- The block, described per configuration. The defaults are the dense
    # block this file has always had: one packed wqkv of equal head counts,
    # no positions, GELU feed-forward, tied head.
    n_kv_heads: int = 0         # 0 = n_heads; fewer = grouped key/value heads
    d_head: int = 0             # 0 = d_model // n_heads
    qk_norm: bool = False       # RMSNorm over each head of q and of k
    rope_theta: float = 0.0     # 0 = no positions; else RoPE, pairs (i, i+d/2)
    mlp: str = "gelu"           # "gelu" (w1, w2) | "swiglu" (w_gate, w_up, w_down)
    tied_head: bool = True      # False: an unembedding of its own ("head")
    indexer: Optional[Indexer] = None   # sparse attention over selected keys
    # -- Layers of several kinds. ``layer_pattern`` is one period of kinds
    # (``_KINDS``), repeated over the depth; () = every layer "attn", the
    # softmax attention the fields above describe. Whatever its mixer, a
    # layer ends in the same feed-forward or expert layer.
    layer_pattern: Tuple[str, ...] = ()
    gdn: Optional[GatedDeltaNet] = None         # describes "gdn" layers
    kda: Optional[KimiDeltaAttention] = None    # describes "kda" layers
    mla: Optional[LatentAttention] = None       # describes "mla" layers
    swa: Optional[SlidingWindow] = None         # describes "swa" layers
    # The first ``dense_layers`` layers end in a dense gated-SiLU
    # feed-forward of width ``dense_ff`` whatever their mixer and whatever
    # ``n_experts`` says of the others (a leading dense layer before
    # expert layers).
    dense_layers: int = 0
    dense_ff: int = 0
    norm_eps: float = 1e-6      # under the root of every RMSNorm
    norm_offset: bool = False   # RMSNorm weights as (1 + w), w from zero
    rope_fraction: float = 1.0  # RoPE over this leading share of a head
    attn_gate: bool = False     # wq is doubled per head: q and a sigmoid
    #                             gate on the attention's output
    shared_expert_ff: int = 0   # >0: a gated expert of this width every
    #                             token takes, behind a sigmoid gate, beside
    #                             the routed ones (never part of a share)
    post_norms: bool = False    # an RMSNorm on the mixer's and on the
    #                             feed-forward's output before each is added
    #                             to the stream (leaves post_ln1, post_ln2)
    embed_scale: float = 1.0    # the input embedding's rows times this (an
    #                             untied table is then drawn at its inverse)
    # Experts (n_experts > 0): each token keeps its moe_top_k; the weights
    # are renormalised over the kept ones or not. experts_held of the
    # n_experts live in this program, from first_expert on, spread over
    # the ``ep`` axis if the mesh has one (0 = all of them): one chip's
    # share of a larger expert-parallel group is experts_held < n_experts
    # on a mesh without ``ep``.
    moe_top_k: int = 1
    moe_renormalize: bool = False
    experts_held: int = 0
    first_expert: int = 0
    # The router's scores (``parallel/moe.moe_ffn``): "softmax" over the
    # experts, or "sigmoid" of each logit; ``moe_select_bias``: a bias a
    # expert ("router_bias", from zero, not trained by the loss; its update
    # rule over an ``ep`` group's load is not built, so it stays zero and
    # changes no selection yet) added to the scores for the SELECTION
    # alone; ``moe_scale`` multiplies the
    # kept weights. ``shared_expert_gate`` False: the shared expert's
    # output is added as it is.
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_scale: float = 1.0
    shared_expert_gate: bool = True
    dtype: Any = jnp.bfloat16
    # "pallas" so TRAINING never materializes [T, T] scores for backward
    # (the flash custom VJP recomputes tiles); untilable shapes still fall
    # back to XLA dense inside flash_attention.
    attn_backend: str = "pallas"
    # Rematerialize each layer in backward, saving only matmul outputs
    # (dots_saveable): recomputes the cheap elementwise chains, trading
    # negligible FLOPs for most of the activation memory.
    remat: bool = False
    # The tied-head unembed matmul dtype. bf16 keeps the [*, vocab] matmul
    # on the fast MXU path (f32 accumulation either way); logits and the
    # softmax stay f32.
    unembed_dtype: Any = jnp.float32
    # >0: compute the LM cross-entropy in vocab chunks of this width with
    # an online log-sum-exp, never materializing the [B, T, vocab] f32
    # logits (2.1 GB at the bench config — the tensor that capped the
    # bench batch at 8). The chunk body is jax.checkpoint'd, so backward
    # recomputes each chunk's logits instead of saving them: ~+1 unembed
    # matmul of FLOPs for O(vocab/chunk) less live memory. Must divide
    # vocab. 0 = dense (one [*, vocab] logits tensor; nll computed as
    # logsumexp - picked_logit, no logp materialization).
    loss_chunk: int = 0


def _axes(mesh: Mesh):
    return set(mesh.axis_names)


def _kv_heads(cfg: TransformerConfig) -> int:
    return cfg.n_kv_heads or cfg.n_heads


def _d_head(cfg: TransformerConfig) -> int:
    return cfg.d_head or cfg.d_model // cfg.n_heads


def _packed_qkv(cfg: TransformerConfig) -> bool:
    """The dense block's attention: one packed ``wqkv`` of equal head
    counts and nothing between the projection and the flash kernel."""
    return not (cfg.n_kv_heads or cfg.d_head or cfg.qk_norm
                or cfg.rope_theta or cfg.indexer or cfg.attn_gate
                or cfg.swa)


def layer_kind(cfg: TransformerConfig, i: int) -> str:
    """The kind of layer ``i`` (from 0): the pattern, repeated."""
    if not cfg.layer_pattern:
        return "attn"
    return cfg.layer_pattern[i % len(cfg.layer_pattern)]


def _check_pattern(cfg: TransformerConfig):
    unknown = set(cfg.layer_pattern) - set(_KINDS)
    if unknown:
        raise ValueError(f"layer_pattern names {sorted(unknown)}; the kinds "
                         f"are {tuple(_KINDS)}")
    for kind, entry in _KINDS.items():
        if entry.field and kind in cfg.layer_pattern:
            described = getattr(cfg, entry.field)
            if described is None:
                raise ValueError(f"layer_pattern has {kind!r} layers and "
                                 f"cfg.{entry.field} does not describe them")
            entry.check(described)
    if cfg.dense_layers and not (cfg.dense_ff and cfg.mlp == "swiglu"):
        raise ValueError("dense_layers are gated-SiLU feed-forwards of "
                         "width dense_ff: they need dense_ff > 0 and "
                         "mlp='swiglu'")
    if cfg.shared_expert_ff and not (cfg.n_experts and cfg.mlp == "swiglu"):
        raise ValueError("shared_expert_ff is a gated expert beside routed "
                         "ones: it needs n_experts > 0 and mlp='swiglu'")


def _keys_split(cfg: TransformerConfig) -> Tuple[int, int]:
    """How many keys a seed is split into, (the model's own, each
    layer's): what fixes the numbers a seed gives every configuration, so
    it grew only with what a configuration uses. The dense block (packed
    ``wqkv``, GELU, tied head): (4, 6); a described block: 12 a layer; with
    what the layer pattern brought: 16; with what the "kda" and "mla"
    kinds brought: 24."""
    wide = bool({"kda", "mla"} & set(cfg.layer_pattern) or cfg.dense_layers
                or cfg.moe_select_bias or cfg.moe_score != "softmax"
                or cfg.moe_scale != 1.0 or not cfg.shared_expert_gate
                or cfg.norm_eps != 1e-6)
    extended = wide or bool(
        cfg.layer_pattern or cfg.norm_offset or cfg.shared_expert_ff
        or cfg.attn_gate or cfg.post_norms or cfg.embed_scale != 1.0)
    if not extended and _packed_qkv(cfg) and cfg.mlp == "gelu" \
            and cfg.tied_head:
        return 4, 6
    return 5, 24 if wide else 16 if extended else 12


def _dense_ffn(cfg: TransformerConfig, i: int) -> bool:
    """Whether layer ``i`` ends in a dense feed-forward."""
    return not cfg.n_experts or i < cfg.dense_layers


def _held(cfg: TransformerConfig) -> int:
    return cfg.experts_held or cfg.n_experts


class _Draw:
    """Draws a model's leaves from the keys a seed was split into, one key
    a leaf in turn (``shard``: :class:`_Specs`)."""

    ones, zeros = staticmethod(jnp.ones), staticmethod(jnp.zeros)

    def __init__(self, cfg: TransformerConfig, keys):
        self._keys, self._next = keys, iter(range(len(keys)))
        # A norm's weight multiplies as it stands (from one) or as 1 + w
        # (from zero): the same function at birth.
        self.gain = jnp.zeros if cfg.norm_offset else jnp.ones

    def normal(self, shape, scale, shard=None):
        return jax.random.normal(self._keys[next(self._next)], shape) * scale

    def log_uniform(self, shape, low, high):
        return jnp.log(jax.random.uniform(self._keys[next(self._next)], shape,
                                          minval=low, maxval=high))


class _Specs:
    """:class:`_Draw`'s walk giving each leaf's PartitionSpec on a mesh:
    ``shard`` "col" / "row" splits its out- / in-dim over tp (Megatron
    column / row), "experts" its experts over ep; any other leaf is
    replicated."""

    def __init__(self, mesh: Mesh):
        tp, ep = (a if a in _axes(mesh) else None for a in ("tp", "ep"))
        self._shards = {"col": P(None, tp), "row": P(tp, None),
                        "experts": P(ep, None, None)}
        self.ones = self.zeros = self.gain = self.log_uniform = \
            lambda *shape_and_range: P()

    def normal(self, shape, scale, shard=None):
        return self._shards.get(shard, P())


def _walk(cfg: TransformerConfig, draw):
    """The parameter tree, each leaf from ``draw`` in the order a seed
    draws them."""
    _check_pattern(cfg)
    d = cfg.d_model
    params: Dict[str, Any] = {
        # A tied table is the unembedding too: 0.02 keeps its logits
        # small. An untied one feeds the residual stream alone, whose
        # branches (fan-in scaled) add outputs of unit size: at 0.02 a
        # token's own row would be a fiftieth of the stream after the
        # first block, and every token's hidden state nearly the same.
        "embed": draw.normal((cfg.vocab, d),
                             0.02 if cfg.tied_head else 1.0 / cfg.embed_scale),
        "lnf": draw.gain((d,)),
        "layers": [],
    }
    if not cfg.tied_head:
        params["head"] = draw.normal((cfg.vocab, d), d ** -0.5)
    for i in range(cfg.n_layers):
        layer = {"ln1": draw.gain((d,)), "ln2": draw.gain((d,))}
        if cfg.post_norms:
            layer.update(post_ln1=draw.gain((d,)), post_ln2=draw.gain((d,)))
        layer.update(_KINDS[layer_kind(cfg, i)].leaves(cfg, draw))
        # Experts: a leading dim of the experts held, sharded over ep.
        experts = not _dense_ffn(cfg, i)
        lead = (_held(cfg),) if experts else ()
        ff = cfg.d_ff if experts or not cfg.dense_layers else cfg.dense_ff
        up, down = ("experts", "experts") if experts else ("col", "row")
        if experts:
            layer["router"] = draw.normal((d, cfg.n_experts), d ** -0.5)
            if cfg.moe_select_bias:
                layer["router_bias"] = draw.zeros((cfg.n_experts,))
        if cfg.mlp == "swiglu":
            layer["w_gate"] = draw.normal(lead + (d, ff), d ** -0.5, up)
            layer["w_up"] = draw.normal(lead + (d, ff), d ** -0.5, up)
            layer["w_down"] = draw.normal(lead + (ff, d), ff ** -0.5, down)
        else:
            layer["w1"] = draw.normal(lead + (d, ff), d ** -0.5, up)
            layer["w2"] = draw.normal(lead + (ff, d), ff ** -0.5, down)
        if cfg.shared_expert_ff and experts:
            f = cfg.shared_expert_ff
            layer["shared_gate"] = draw.normal((d, f), d ** -0.5)
            layer["shared_up"] = draw.normal((d, f), d ** -0.5)
            layer["shared_down"] = draw.normal((f, d), f ** -0.5)
            if cfg.shared_expert_gate:
                layer["shared_w"] = draw.normal((d, 1), d ** -0.5)
        params["layers"].append(layer)
    return params


def init_params(rng, cfg: TransformerConfig) -> Dict:
    """Global (unsharded-shape) parameter pytree; place with
    :func:`param_specs` + ``jax.device_put`` before use. Layer ``i``'s
    entry has the leaves of its kind (:func:`layer_kind`)."""
    own, per_layer = _keys_split(cfg)
    return _walk(cfg, _Draw(cfg, jax.random.split(
        rng, own + per_layer * cfg.n_layers)))


def param_specs(cfg: TransformerConfig, mesh: Mesh) -> Dict:
    """PartitionSpec tree matching :func:`init_params`: Megatron column
    (out-dim) / row (in-dim) sharding over tp; experts over ep; everything
    else replicated (dp/sp replicate params; a mixer's leaves are
    replicated unless its kind names their shard)."""
    return _walk(cfg, _Specs(mesh))


def mla_from_interleaved(params, cfg: TransformerConfig,
                         inverse: bool = False):
    """Parameters whose latent attention rotates the interleaved pairs
    (2i, 2i + 1) of its rotated columns (the DeepSeek-V3 block's
    checkpoints: ``rope_interleave``) as this file's, which rotate the
    pairs (i, i + d/2) at the same frequency: the even columns first, then
    the odd ones, of each query head's trailing ``d_shared`` columns of
    ``mla_wq`` and of the last ``d_shared`` of ``mla_wkva``. A fixed
    permutation, so that both compute the same function. ``inverse``: this
    file's parameters as such a checkpoint."""
    m = cfg.mla
    if m is None or not m.rope_theta:
        return params
    d, width = m.d_shared, m.d_nope + m.d_shared
    order = list(range(0, d, 2)) + list(range(1, d, 2))
    if inverse:
        order = sorted(range(d), key=order.__getitem__)
    q_cols = [h * width + j for h in range(cfg.n_heads)
              for j in [*range(m.d_nope), *(m.d_nope + o for o in order)]]
    kva_cols = [*range(m.kv_rank), *(m.kv_rank + o for o in order)]
    layers = []
    for i, layer in enumerate(params["layers"]):
        if layer_kind(cfg, i) == "mla":
            layer = dict(layer,
                         mla_wq=jnp.take(layer["mla_wq"], jnp.array(q_cols),
                                         axis=1),
                         mla_wkva=jnp.take(layer["mla_wkva"],
                                           jnp.array(kva_cols), axis=1))
        layers.append(layer)
    return dict(params, layers=layers)


def gate_from_projection(params, cfg: TransformerConfig,
                         inverse: bool = False):
    """Parameters whose attention output gate is a projection of its own
    (a leaf ``w_attn_gate`` [d, H dh] beside ``wq`` [d, H dh], heads-major
    both: the afmoe block's ``gate_proj``) as this file's, whose ``wq``
    holds each head's query columns and then its gate's
    (``TransformerConfig.attn_gate``): a fixed permutation of columns, so
    that both compute the same function. ``inverse``: this file's
    parameters as such a checkpoint."""
    if not cfg.attn_gate:
        return params
    H, dh = cfg.n_heads, _d_head(cfg)
    layers = []
    for layer in params["layers"]:
        if "wq" in layer and inverse:
            d = layer["wq"].shape[0]
            both = layer["wq"].reshape(d, H, 2, dh)
            layer = dict(layer, wq=both[:, :, 0].reshape(d, H * dh),
                         w_attn_gate=both[:, :, 1].reshape(d, H * dh))
        elif "wq" in layer:
            layer = dict(layer)
            d, gate = layer["wq"].shape[0], layer.pop("w_attn_gate")
            layer["wq"] = jnp.stack(
                [layer["wq"].reshape(d, H, dh), gate.reshape(d, H, dh)],
                axis=2).reshape(d, H * 2 * dh)
        layers.append(layer)
    return dict(params, layers=layers)


def _rms_norm(x, scale, offset: bool = False, eps: float = 1e-6):
    """RMSNorm, float32 inside; ``offset``: the weight multiplies as
    ``1 + scale`` (``TransformerConfig.norm_offset``)."""
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * (1.0 + scale if offset else scale)).astype(x.dtype)


def _layer_norm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) / jnp.sqrt(var + 1e-6) * scale + bias).astype(x.dtype)


def _rope(x, theta: float, fraction: float = 1.0):
    """Rotate the pairs (i, i + d/2) of the last dim of ``x`` [B, T, ..., d]
    by position t, base ``theta`` (float32 inside, x's dtype out). With
    ``fraction`` < 1 the leading ``fraction`` of the last dim is rotated so
    (pairs (i, i + r/2) of its r) and the rest passes through."""
    if fraction != 1.0:
        r = int(x.shape[-1] * fraction)
        return jnp.concatenate([_rope(x[..., :r], theta), x[..., r:]],
                               axis=-1)
    T, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, T) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a = x[..., :d // 2].astype(jnp.float32)
    b = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _output_gate(o, gate):
    """The attention's output gate, ``o * sigmoid(gate)``, float32 inside
    (``TransformerConfig.attn_gate``)."""
    return o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))


def _l2_norm(x):
    """x / |x| over the last dim (float32 inside, eps 1e-6 under the
    root)."""
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                            + 1e-6)).astype(x.dtype)


def shared_expert(layer, h, dtype):
    """The gated expert every token takes, behind its sigmoid gate:
    ``sigmoid(h w_s) (silu(h Wg) * h Wu) Wd``; without the leaf
    ``shared_w`` (``shared_expert_gate`` False), as it is. Every chip of
    an expert-parallel group computes it for its own tokens: it is no
    part of a share."""
    with jax.named_scope("moe.shared"):
        def dot(a, w):
            return a @ layer[w].astype(dtype)
        act = jax.nn.silu(dot(h, "shared_gate")) * dot(h, "shared_up")
        if "shared_w" not in layer:
            return dot(act, "shared_down")
        gate = jax.nn.sigmoid(dot(h, "shared_w").astype(jnp.float32))
        return (dot(act, "shared_down").astype(jnp.float32)
                * gate).astype(dtype)


@dataclasses.dataclass(frozen=True)
class _Ctx:
    """What a mixer sees beside its layer: the configuration, the mesh's
    share of the heads, and ``with_masks`` (hand back in ``extras`` what
    went into the attention or the rule and what came out)."""
    cfg: TransformerConfig
    has_tp: bool
    has_sp: bool
    tp_size: int
    n_heads_local: int
    d_head: int
    with_masks: bool


def _attn_leaves(cfg: TransformerConfig, draw, indexer=True) -> Dict:
    """The softmax attention's projections: one packed ``wqkv`` (the dense
    block) or separate ones; and the indexer's, if any and ``indexer``."""
    d, dh = cfg.d_model, _d_head(cfg)
    if _packed_qkv(cfg):
        return {"wqkv": draw.normal((d, 3 * d), d ** -0.5, "col"),
                "wo": draw.normal((d, d), d ** -0.5, "row")}
    # Head-major columns, as wqkv: a tp column slice holds whole heads
    # (with attn_gate, each head's q then its gate).
    nq, nkv = cfg.n_heads * dh, _kv_heads(cfg) * dh
    layer = {"wq": draw.normal((d, nq * (2 if cfg.attn_gate else 1)),
                               d ** -0.5, "col"),
             "wk": draw.normal((d, nkv), d ** -0.5, "col"),
             "wv": draw.normal((d, nkv), d ** -0.5, "col"),
             "wo": draw.normal((nq, d), nq ** -0.5, "row")}
    if cfg.qk_norm:
        layer.update(q_norm=draw.gain((dh,)), k_norm=draw.gain((dh,)))
    if cfg.indexer and indexer:
        ix = cfg.indexer
        layer.update(
            idx_wq=draw.normal((d, ix.n_heads * ix.d_head), d ** -0.5),
            idx_wk=draw.normal((d, ix.d_head), d ** -0.5),
            idx_ww=draw.normal((d, ix.n_heads), d ** -0.5),
            idx_k_scale=draw.ones((ix.d_head,)),
            idx_k_bias=draw.zeros((ix.d_head,)))
    return layer


def _packed_attention(layer, h, ctx: _Ctx):
    """The dense block's attention from its packed ``wqkv``: h [B, T, D]
    -> [B, T, H·dh] of this tp shard's heads."""
    from ..ops.pallas_attention import (flash_attention,
                                        flash_attention_qkv,
                                        qkv_flash_tilable)
    cfg, n_heads_local, d_head = ctx.cfg, ctx.n_heads_local, ctx.d_head
    qkv = h @ layer["wqkv"].astype(cfg.dtype)     # [B, T, 3·D/tp]
    B, T, _ = qkv.shape
    # HEAD-major column layout [D, H, 3, dh]: a tp column-slice holds
    # whole heads (each with its own q,k,v), so the sharded model
    # computes the SAME function as tp=1 from the same weights
    # (checkpoints stay portable across mesh shapes).
    if (not ctx.has_sp and cfg.attn_backend == "pallas"
            and qkv_flash_tilable(T, d_head)):
        # Packed path: the kernel consumes the projection output
        # directly (head-major columns) and returns [B, T, H·dh] — no
        # [B,T,H,dh] <-> [BH,T,dh] transposes on either side
        # (~11 ms/step of layout copies at the LM bench config).
        return flash_attention_qkv(qkv, n_heads_local,
                                   causal=True).astype(cfg.dtype)
    qkv = qkv.reshape(B, T, n_heads_local, 3, d_head)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    if ctx.has_sp:
        attn = ring_attention(q, k, v, axis_name="sp", causal=True)
    else:
        # Single-shard attention: the Pallas blockwise kernel by
        # default (scores never hit HBM in forward OR backward);
        # untilable shapes fall back to XLA dense inside.
        attn = flash_attention(q, k, v, causal=True,
                               backend=cfg.attn_backend).astype(cfg.dtype)
    return attn.reshape(B, T, n_heads_local * d_head)


def _projected_attention(layer, h, extras, ctx: _Ctx, rope_theta,
                         window=None):
    """Separate q/k/v projections: grouped key/value heads, per-head
    RMSNorm, RoPE of base ``rope_theta``, and dense, windowed (``window``
    keys; never the [T, T] scores under the "pallas" backend) or
    indexer-selected attention."""
    from ..ops.pallas_attention import flash_attention
    from ..ops.sparse_attention import dsa_attention
    cfg, n_heads_local, d_head = ctx.cfg, ctx.n_heads_local, ctx.d_head
    B, T, _ = h.shape
    kv_local = _kv_heads(cfg) // ctx.tp_size

    def heads(w, n, width=d_head):
        return (h @ layer[w].astype(cfg.dtype)).reshape(B, T, n, width)
    if cfg.attn_gate:
        q = heads("wq", n_heads_local, 2 * d_head)
        q, gate = q[..., :d_head], q[..., d_head:]
    else:
        q = heads("wq", n_heads_local)
    k, v = heads("wk", kv_local), heads("wv", kv_local)
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.norm_offset)
        k = _rms_norm(k, layer["k_norm"], cfg.norm_offset)
    if rope_theta:
        q = _rope(q, rope_theta, cfg.rope_fraction)
        k = _rope(k, rope_theta, cfg.rope_fraction)
    if cfg.indexer:
        ix = cfg.indexer
        hs = lax.stop_gradient(h)
        with jax.named_scope("attn.indexer"):
            qi = (hs @ layer["idx_wq"].astype(cfg.dtype)).reshape(
                B, T, ix.n_heads, ix.d_head)
            ki = _layer_norm(hs @ layer["idx_wk"].astype(cfg.dtype),
                             layer["idx_k_scale"], layer["idx_k_bias"])
            if cfg.rope_theta:
                qi, ki = _rope(qi, cfg.rope_theta), _rope(ki, cfg.rope_theta)
            w = (hs @ layer["idx_ww"].astype(cfg.dtype)).astype(
                jnp.float32) * (ix.n_heads * ix.d_head) ** -0.5
        attn, extras["kl_sum"], extras["kl"], mask = dsa_attention(
            q, k, v, qi, ki, w, topk=ix.topk, backend=cfg.attn_backend)
        if ctx.with_masks:
            extras["mask"] = mask
    else:
        group = n_heads_local // kv_local
        qkv = (q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2))
        attn = flash_attention(*qkv, causal=True, backend=cfg.attn_backend,
                               fallback=window is None, window=window)
        if ctx.with_masks:
            extras["attn_in"], extras["attn_o"] = qkv, attn
    if cfg.attn_gate:
        attn = _output_gate(attn, gate)
    return attn.astype(cfg.dtype).reshape(B, T, n_heads_local * d_head)


def _attn_mixer(li, layer, h, extras, ctx: _Ctx):
    cfg = ctx.cfg
    # Among layers of several kinds the softmax layer has a scope of its
    # own.
    with (jax.named_scope("attn.full") if cfg.layer_pattern
          else contextlib.nullcontext()):
        attn = (_packed_attention(layer, h, ctx) if _packed_qkv(cfg) else
                _projected_attention(layer, h, extras, ctx, cfg.rope_theta))
        proj = attn @ layer["wo"].astype(cfg.dtype)
    if ctx.has_tp:
        proj = lax.psum(proj, "tp")               # row-parallel combine
    return proj


def _swa_mixer(li, layer, h, extras, ctx: _Ctx):
    from ..ops.pallas_attention import record_tile_pairs
    cfg, swa = ctx.cfg, ctx.cfg.swa
    record_tile_pairs(li, h.shape[1], swa.window)
    with jax.named_scope("attn.swa"):
        return _projected_attention(
            layer, h, extras, ctx, swa.rope_theta,
            swa.window) @ layer["wo"].astype(cfg.dtype)


def _gdn_leaves(cfg: TransformerConfig, draw) -> Dict:
    g, d = cfg.gdn, cfg.d_model
    nk, nv = g.n_k_heads * g.d_k, g.n_v_heads * g.d_v
    # Columns [q | k | v | z], heads-major inside each; [b | a].
    return {"gdn_wqkvz": draw.normal((d, 2 * nk + 2 * nv), d ** -0.5),
            "gdn_wba": draw.normal((d, 2 * g.n_v_heads), d ** -0.5),
            "gdn_conv": draw.normal((g.conv_width, 2 * nk + nv),
                                    g.conv_width ** -0.5),
            # As the published module draws them: A uniform in (0, 16),
            # kept as its logarithm; the step's bias at one.
            "gdn_a_log": draw.log_uniform((g.n_v_heads,), 1e-3, 16.0),
            "gdn_dt_bias": draw.ones((g.n_v_heads,)),
            "gdn_norm": draw.ones((g.d_v,)),      # plain weight
            "gdn_wout": draw.normal((nv, d), nv ** -0.5)}


def _check_gdn(g: GatedDeltaNet):
    if g.n_v_heads % g.n_k_heads:
        raise ValueError(f"gdn: {g.n_v_heads} value heads do not divide "
                         f"over {g.n_k_heads} key heads")


def _gdn_mixer(li, layer, h, extras, ctx: _Ctx):
    """The Gated DeltaNet mixer: h [B, T, D] -> [B, T, D]."""
    from ..ops.causal_conv import causal_conv_silu
    from ..ops.gated_delta import gated_delta_rule, record_saved
    cfg = ctx.cfg
    g, f32 = cfg.gdn, jnp.float32
    B, T, _ = h.shape
    nk, nv = g.n_k_heads * g.d_k, g.n_v_heads * g.d_v
    with jax.named_scope("gdn.proj"):
        qkvz = h @ layer["gdn_wqkvz"].astype(cfg.dtype)
        ba = (h @ layer["gdn_wba"].astype(cfg.dtype)).astype(f32)
    # What lies between the projections and the rule, and between the
    # rule and the output projection, is elementwise over 12288 columns a
    # row: it is recomputed in the backward from the projections' outputs
    # (taken whole: a slice would be a copy; the convolution reads its
    # 2 nk + nv columns of qkvz where they lie), not saved (3.4 GB over
    # three layers at 2 x 8192 rows, compiled for a v5e).
    @jax.checkpoint
    def conv_and_gates(qkvz, ba, taps, a_log, dt_bias):
        with jax.named_scope("gdn.conv"):
            qkv = causal_conv_silu(qkvz, taps, backend=g.backend, layer=li)
        with jax.named_scope("gdn.scan"):
            q = qkv[..., :nk].reshape(B, T, g.n_k_heads, g.d_k)
            k = qkv[..., nk:2 * nk].reshape(B, T, g.n_k_heads, g.d_k)
            v = qkv[..., 2 * nk:].reshape(B, T, g.n_v_heads, g.d_v)
            beta = jax.nn.sigmoid(ba[..., :g.n_v_heads])
            decay = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                ba[..., g.n_v_heads:] + dt_bias.astype(f32))
            return (_l2_norm(q) * g.d_k ** -0.5, _l2_norm(k), v, decay, beta)

    @jax.checkpoint
    def gated_norm(o, qkvz, scale):
        z = qkvz[..., 2 * nk + nv:].reshape(B, T, g.n_v_heads, g.d_v)
        o = _rms_norm(o.astype(f32), scale) * jax.nn.silu(z.astype(f32))
        return o.astype(cfg.dtype).reshape(B, T, nv)

    q, k, v, decay, beta = conv_and_gates(
        qkvz, ba, layer["gdn_conv"], layer["gdn_a_log"], layer["gdn_dt_bias"])
    with jax.named_scope("gdn.scan"):
        record_saved(li, q.shape, g.n_v_heads, g.d_v,
                     jnp.dtype(cfg.dtype).itemsize, g.chunk)
        # Each key head serves n_v_heads / n_k_heads consecutive value
        # heads (inside the rule).
        o = gated_delta_rule(q, k, v, decay, beta, chunk=g.chunk,
                             backend=g.backend, layer=li)
        if ctx.with_masks:
            extras["gdn_o"] = o
    with jax.named_scope("gdn.out"):
        return gated_norm(o, qkvz, layer["gdn_norm"]) \
            @ layer["gdn_wout"].astype(cfg.dtype)


def _kda_leaves(cfg: TransformerConfig, draw) -> Dict:
    a, d = cfg.kda, cfg.d_model
    n, r = a.n_heads * a.d_head, a.d_head
    # Columns [q | k | v], heads-major inside each; then the low-rank
    # pairs of the decay (f) and of the output gate.
    return {"kda_wqkv": draw.normal((d, 3 * n), d ** -0.5),
            "kda_conv": draw.normal((a.conv_width, 3 * n),
                                    a.conv_width ** -0.5),
            "kda_wf_down": draw.normal((d, r), d ** -0.5),
            "kda_wf_up": draw.normal((r, n), r ** -0.5),
            "kda_wg_down": draw.normal((d, r), d ** -0.5),
            "kda_wg_up": draw.normal((r, n), r ** -0.5),
            "kda_wbeta": draw.normal((d, a.n_heads), d ** -0.5),
            # As the published module draws them: A uniform in (1, 16), a
            # head, kept as its logarithm; the step's bias, a channel, at
            # one.
            "kda_a_log": draw.log_uniform((a.n_heads,), 1.0, 16.0),
            "kda_dt_bias": draw.ones((n,)),
            "kda_norm": draw.ones((a.d_head,)),
            "kda_wout": draw.normal((n, d), n ** -0.5)}


def _kda_mixer(li, layer, h, extras, ctx: _Ctx):
    """The Kimi Delta Attention mixer: h [B, T, D] -> [B, T, D]."""
    from ..ops.causal_conv import causal_conv_silu
    from ..ops.gated_delta import gated_delta_rule, record_saved
    cfg = ctx.cfg
    a, f32, eps = cfg.kda, jnp.float32, cfg.norm_eps
    B, T, _ = h.shape
    H, dh = a.n_heads, a.d_head
    n = H * dh

    def dot(x, w):
        return x @ layer[w].astype(cfg.dtype)
    with jax.named_scope("kda.proj"):
        qkv = dot(h, "kda_wqkv")
        f_low, g_low = dot(h, "kda_wf_down"), dot(h, "kda_wg_down")
        b = dot(h, "kda_wbeta").astype(f32)
    # As in the Gated DeltaNet mixer, what is elementwise between a
    # projection and the rule, and between the rule and the output
    # projection, is recomputed in the backward. The low-rank pairs' second
    # halves are in there too: what is kept of the decay and of the output
    # gate is their d_head-wide first half, not H x d_head columns a row.
    @jax.checkpoint
    def conv_and_gates(qkv, f_low, b, taps, wf_up, a_log, dt_bias):
        with jax.named_scope("kda.proj"):
            f = (f_low @ wf_up.astype(cfg.dtype)).astype(f32)
        with jax.named_scope("kda.conv"):
            qkv = causal_conv_silu(qkv, taps, backend=a.backend, layer=li)
        with jax.named_scope("kda.scan"):
            q, k, v = (qkv[..., j * n:(j + 1) * n].reshape(B, T, H, dh)
                       for j in range(3))
            decay = -jnp.exp(a_log.astype(f32))[:, None] \
                * jax.nn.softplus(f.reshape(B, T, H, dh)
                                  + dt_bias.astype(f32).reshape(H, dh))
            return (_l2_norm(q) * dh ** -0.5, _l2_norm(k), v, decay,
                    jax.nn.sigmoid(b))

    @jax.checkpoint
    def gated_norm(o, g_low, wg_up, scale):
        with jax.named_scope("kda.proj"):
            gate = (g_low @ wg_up.astype(cfg.dtype)).astype(f32)
        with jax.named_scope("kda.out"):
            o = _rms_norm(o.astype(f32), scale, eps=eps) \
                * jax.nn.sigmoid(gate.reshape(B, T, H, dh))
            return o.astype(cfg.dtype).reshape(B, T, n)

    q, k, v, decay, beta = conv_and_gates(
        qkv, f_low, b, layer["kda_conv"], layer["kda_wf_up"],
        layer["kda_a_log"], layer["kda_dt_bias"])
    with jax.named_scope("kda.scan"):
        record_saved(li, q.shape, H, dh, jnp.dtype(cfg.dtype).itemsize,
                     a.chunk, per_channel=True)
        o = gated_delta_rule(q, k, v, decay, beta, chunk=a.chunk,
                             backend=a.backend, layer=li)
        if ctx.with_masks:
            extras["kda_in"], extras["kda_o"] = (q, k, v, decay, beta), o
    out = gated_norm(o, g_low, layer["kda_wg_up"], layer["kda_norm"])
    with jax.named_scope("kda.out"):
        return dot(out, "kda_wout")


def _mla_leaves(cfg: TransformerConfig, draw) -> Dict:
    m, H, d = cfg.mla, cfg.n_heads, cfg.d_model
    return {"mla_wq": draw.normal((d, H * (m.d_nope + m.d_shared)),
                                  d ** -0.5),
            # Columns [latent | shared key part].
            "mla_wkva": draw.normal((d, m.kv_rank + m.d_shared), d ** -0.5),
            "mla_kv_norm": draw.gain((m.kv_rank,)),
            # Columns heads-major, each head's [key part | value].
            "mla_wkvb": draw.normal((m.kv_rank, H * (m.d_nope + m.d_v)),
                                    m.kv_rank ** -0.5),
            "mla_wo": draw.normal((H * m.d_v, d), (H * m.d_v) ** -0.5)}


def _mla_mixer(li, layer, h, extras, ctx: _Ctx):
    """Latent attention: h [B, T, D] -> [B, T, D].
    Never the [T, T] scores: a shape the flash kernels cannot tile is an
    error under ``attn_backend="pallas"``."""
    from ..ops.pallas_attention import flash_attention
    cfg = ctx.cfg
    m, H = cfg.mla, cfg.n_heads
    B, T, _ = h.shape

    def dot(x, w):
        return x @ layer[w].astype(cfg.dtype)
    with jax.named_scope("attn.mla"):
        q = dot(h, "mla_wq").reshape(B, T, H, m.d_nope + m.d_shared)
        latent = dot(h, "mla_wkva")
        kv = dot(_rms_norm(latent[..., :m.kv_rank], layer["mla_kv_norm"],
                           cfg.norm_offset, cfg.norm_eps), "mla_wkvb").reshape(
            B, T, H, m.d_nope + m.d_v)
        shared = latent[:, :, None, m.kv_rank:]
        if m.rope_theta:
            # The shared key part is rotated ONCE a token, before it goes
            # to the heads: its gradient is summed over the heads first.
            with jax.named_scope("mla.rope"):
                q = jnp.concatenate(
                    [q[..., :m.d_nope],
                     _rope(q[..., m.d_nope:], m.rope_theta)], axis=-1)
                shared = _rope(shared, m.rope_theta)
        # One shared key part a token, the same for every head.
        shared = jnp.broadcast_to(shared, (B, T, H, m.d_shared))
        k = jnp.concatenate([kv[..., :m.d_nope], shared], axis=-1)
        v = kv[..., m.d_nope:]
        o = flash_attention(q, k, v, causal=True, backend=cfg.attn_backend,
                            fallback=False)
        if ctx.with_masks:
            extras["mla_in"], extras["mla_o"] = (q, k, v), o
        return dot(o.astype(cfg.dtype).reshape(B, T, H * m.d_v), "mla_wo")


class _Kind(NamedTuple):
    """A layer kind, all this file knows of it: ``field``, the
    :class:`TransformerConfig` field that describes its layers (None: the
    configuration's own fields do), which ``check`` holds to more; the mesh
    axes it ``refuses``, and ``why``; ``leaves(cfg, draw)``, its mixer's
    parameters in the order a seed draws them (:class:`_Draw`);
    ``forward(li, layer, h, extras, ctx)``, the mixer, h [B, T, D] ->
    [B, T, D] under the kind's own scopes and counters."""
    field: Optional[str]
    leaves: Callable[[TransformerConfig, Any], Dict]
    forward: Callable[..., Any]
    refuses: Tuple[str, ...] = ()
    why: str = ""
    check: Callable[[Any], None] = lambda described: None


# Why a layer whose state runs along the sequence refuses sp and tp.
_STATE_WHY = ("layer's state runs over the whole sequence and its heads "
              "are not sharded: no sp and no tp (dp and ep only)")

# The kinds ``layer_pattern`` may name.
_KINDS = {
    "attn": _Kind(None, _attn_leaves, _attn_mixer),
    "gdn": _Kind("gdn", _gdn_leaves, _gdn_mixer, ("sp", "tp"),
                 f"a 'gdn' {_STATE_WHY}", _check_gdn),
    "kda": _Kind("kda", _kda_leaves, _kda_mixer, ("sp", "tp"),
                 f"a 'kda' {_STATE_WHY}"),
    "mla": _Kind("mla", _mla_leaves, _mla_mixer, ("sp", "tp"),
                 "an 'mla' layer expands its latent for every head over the "
                 "whole sequence: no sp and no tp (dp and ep only)"),
    "swa": _Kind("swa", functools.partial(_attn_leaves, indexer=False),
                 _swa_mixer, ("sp", "tp"),
                 "an 'swa' layer's band is not passed around an sp ring and "
                 "its output is not summed over tp: no sp and no tp (dp and "
                 "ep only)"),
}


def _check_mesh(cfg: TransformerConfig, axes):
    """Refuse the mesh axes a configuration's layers cannot take."""
    kinds = {layer_kind(cfg, i) for i in range(cfg.n_layers)}
    for kind, entry in _KINDS.items():
        if kind in kinds and set(entry.refuses) & set(axes):
            raise NotImplementedError(entry.why)
    if cfg.dense_layers and "tp" in axes:
        raise NotImplementedError(
            "dense_layers beside expert layers are not sharded over tp")
    # The "attn" kind's refusals follow from the fields that describe it.
    if not _packed_qkv(cfg) and "sp" in axes:
        raise NotImplementedError(
            "ring attention over sp takes the dense block only (no RoPE "
            "offset, no grouped heads, no indexer, no output gate)")
    if cfg.indexer and "tp" in axes:
        raise NotImplementedError(
            "sparse attention's selection and KL are over all heads: no tp")


def _forward_layers(params, tokens, cfg: TransformerConfig, mesh: Mesh,
                    with_masks: bool = False, grad_sync=None):
    """Runs INSIDE shard_map: ``tokens`` [B_local, T_local] int32. Returns
    the final hidden states [B_local, T_local, d_model] (normed, before
    the unembedding) and, per layer, a dict of what the block produced
    beside them: ``aux`` (the experts' balance loss), ``kl_sum`` and ``kl``
    (the indexer's KL summed over each sequence's rows, [B], which the loss
    differentiates, and per row, [B, T], which it does not), the routing
    load ``held_load`` / ``absent`` and the experts each token kept
    ``ids``, and with ``with_masks`` the int8 selection ``mask`` and a
    delta-rule layer's rule output ``gdn_o`` / ``kda_o`` [B, T, Hv, dv]
    and an "mla" layer's attention output ``mla_o`` [B, T, H, d_v], with
    what went into them: ``kda_in`` (q, k, v, g, beta as the rule takes
    them) and ``mla_in`` (q, k, v as the attention does).

    ``grad_sync(k, layer, x, carry) -> (layer, x, carry)``, an identity
    here, is the training step's hook for the gradient exchange
    (:func:`make_parallel_train_step`): it sees layer ``k``'s parameters
    and the activations that enter the layer, and threads its carry from
    the lowest layer to the highest."""
    axes = _axes(mesh)
    _check_mesh(cfg, axes)
    tp_size = mesh.shape.get("tp", 1)
    ctx = _Ctx(cfg=cfg, has_tp="tp" in axes, has_sp="sp" in axes,
               tp_size=tp_size,
               n_heads_local=cfg.n_heads // tp_size, d_head=_d_head(cfg),
               with_masks=with_masks)
    offset, eps = cfg.norm_offset, cfg.norm_eps

    def _layer_fwd(li, layer, x):
        extras = {}
        h = _rms_norm(x, layer["ln1"], offset, eps)
        proj = _KINDS[layer_kind(cfg, li)].forward(li, layer, h, extras, ctx)
        if cfg.post_norms:
            proj = _rms_norm(proj, layer["post_ln1"], offset, eps)
        x = x + proj
        h = _rms_norm(x, layer["ln2"], offset, eps)
        gated = cfg.mlp == "swiglu"
        w_up, w_down = ("w_up", "w_down") if gated else ("w1", "w2")
        if not _dense_ffn(cfg, li):
            B, T, _ = h.shape
            y, stats = moe_ffn(
                h.reshape(-1, cfg.d_model), layer["router"],
                layer[w_up].astype(cfg.dtype), layer[w_down].astype(cfg.dtype),
                w_gate=layer["w_gate"].astype(cfg.dtype) if gated else None,
                top_k=cfg.moe_top_k, renormalize=cfg.moe_renormalize,
                first_expert=cfg.first_expert,
                axis_name="ep" if "ep" in axes else None,
                score=cfg.moe_score, scale=cfg.moe_scale,
                select_bias=layer.get("router_bias"))
            extras.update(stats)
            y = y.reshape(B, T, cfg.d_model)
            if cfg.shared_expert_ff:
                y = y + shared_expert(layer, h, cfg.dtype)
        else:
            # Among expert layers a dense feed-forward has a scope of its
            # own.
            with (jax.named_scope("ffn.dense") if cfg.dense_layers
                  else contextlib.nullcontext()):
                up = h @ layer[w_up].astype(cfg.dtype)
                if gated:
                    up = jax.nn.silu(h @ layer["w_gate"].astype(cfg.dtype)) \
                        * up
                else:
                    up = jax.nn.gelu(up)
                y = up @ layer[w_down].astype(cfg.dtype)
            if ctx.has_tp:
                y = lax.psum(y, "tp")
        if cfg.post_norms:
            y = _rms_norm(y, layer["post_ln2"], offset, eps)
        return x + y, extras

    def _layer(li):
        fwd = functools.partial(_layer_fwd, li)
        if cfg.remat:
            fwd = jax.checkpoint(
                fwd, policy=jax.checkpoint_policies.dots_saveable)
        return fwd

    x = params["embed"][tokens]                       # [B, T, D]
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    x = x.astype(cfg.dtype)
    carry = None
    per_layer = []
    for k, layer in enumerate(params["layers"]):
        if grad_sync is not None:
            layer, x, carry = grad_sync(k, layer, x, carry)
        x, extras = _layer(k)(layer, x)
        per_layer.append(extras)
    return _rms_norm(x, params["lnf"], offset, eps), per_layer


def _aux_total(per_layer):
    return sum((e["aux"] for e in per_layer if "aux" in e),
               jnp.zeros((), jnp.float32))


def _balance_counter():
    from ..obs.registry import registry
    return registry().counter(
        "hvd_moe_balance_loss_total",
        "traces of the training loss of a model with expert layers, by "
        "whether the load-balancing loss was built into it (no: its weight "
        "is a static zero)", labels=("built",))


def forward_hidden(params, tokens, cfg: TransformerConfig, mesh: Mesh):
    """Runs INSIDE shard_map: ``tokens`` [B_local, T_local] int32.
    Returns (final hidden states [B_local, T_local, d_model] — the
    pre-unembed activations — and the MoE aux loss). The chunked-loss
    path consumes this directly so the [*, vocab] logits never
    materialize; :func:`forward` layers the unembedding on top."""
    x, per_layer = _forward_layers(params, tokens, cfg, mesh)
    return x, _aux_total(per_layer)


def _unembedding(params, cfg: TransformerConfig):
    """The [vocab, d_model] matrix the logits are taken against: the
    embedding itself (tied) or the head of its own."""
    return params["embed"] if cfg.tied_head else params["head"]


def _logits(x, params, cfg: TransformerConfig):
    # bf16 MXU pass with f32 accumulation when unembed_dtype is bf16;
    # logits are f32 either way for a stable softmax.
    return jnp.matmul(x.astype(cfg.unembed_dtype),
                      _unembedding(params, cfg).T.astype(cfg.unembed_dtype),
                      preferred_element_type=jnp.float32)


def _over_mesh(fn, cfg: TransformerConfig, mesh: Mesh):
    """``fn(params, tokens)``, written for the inside of a shard_map, as a
    function of global arrays: parameters by :func:`param_specs`, tokens
    replicated (every device computes every sequence; the sequence axis
    over ``sp``), outputs likewise. A Mosaic kernel cannot be partitioned
    by the compiler, so on several devices the forward must be mapped.
    ``fn`` returns (an array over the sequence, a scalar)."""
    if mesh.size == 1:
        return fn
    seq = P(None, "sp" if "sp" in _axes(mesh) else None)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(param_specs(cfg, mesh), seq),
                         out_specs=(seq, P()), check_vma=False)


def forward(params, tokens, cfg: TransformerConfig, mesh: Mesh):
    """Full forward of GLOBAL arrays, for an evaluation beside the step:
    hidden states through the unembedding. Returns (logits [B, T, vocab],
    moe_aux_loss); on a mesh of several devices every device computes every
    sequence. Inside a shard_map, :func:`forward_hidden` is the function."""
    def local(p, t):
        x, aux_total = forward_hidden(p, t, cfg, mesh)
        return _logits(x, p, cfg), aux_total
    return _over_mesh(local, cfg, mesh)(params, tokens)


def forward_with_stats(params, tokens, cfg: TransformerConfig, mesh: Mesh):
    """The training forward with what each block produced beside the
    hidden states: (logits, per-layer dicts as :func:`_forward_layers`
    gives them, selections included). For checks and counters, off the
    step's path."""
    x, per_layer = _forward_layers(params, tokens, cfg, mesh,
                                   with_masks=True)
    return _logits(x, params, cfg), per_layer


def dense_nll(logits, labels):
    """Per-token -log p(label): lse - picked_logit, NOT
    -take(log_softmax) — the log_softmax form materializes a full
    [*, vocab] f32 logp tensor (2.1 GB at the bench config, profiled at
    ~6.5 ms/step of pure HBM) only to gather one element per row.
    logsumexp reduces in one pass and the gather reads the raw logits;
    gradients are identical (softmax - onehot) either way. Shared by the
    dp/sp/tp/ep family here and the pipeline family's head loss
    (``pp_transformer.py``)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
    return lse - picked


def chunked_nll(x, embed, labels, cfg: TransformerConfig):
    """Per-token −log p(label) over a tied unembedding, computed in vocab
    chunks with an online log-sum-exp so the [N, vocab] f32 logits never
    exist at once (the memory-bound tensor of LM training; the same
    running max/sum recurrence flash attention uses, applied to the loss).

    The chunk body is ``jax.checkpoint``'d: autodiff through the scan
    would otherwise stash every chunk's logits — the full logits tensor
    again — as residuals; with remat, backward replays each chunk's
    unembed matmul instead (one extra [N, d] × [d, C] pass per chunk).
    """
    orig_shape = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    vocab = embed.shape[0]
    # Clamp labels into [0, vocab): the dense path's take_along_axis clips
    # out-of-range indices to a real logit, while an unclamped chunked scan
    # would treat such a label as absent from every chunk (ll stays 0, nll
    # becomes the full lse) — toggling loss_chunk must not change the loss
    # on any input.
    lab = jnp.clip(labels.reshape(-1), 0, vocab - 1)
    n = xf.shape[0]
    chunk = cfg.loss_chunk
    if vocab % chunk:
        raise ValueError(
            f"loss_chunk={chunk} must divide vocab={vocab}")
    n_chunks = vocab // chunk
    wch = embed.reshape(n_chunks, chunk, d)

    def body(carry, inp):
        m, s, ll = carry
        i, w = inp
        logits = jnp.matmul(xf.astype(cfg.unembed_dtype),
                            w.T.astype(cfg.unembed_dtype),
                            preferred_element_type=jnp.float32)  # [N, C]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1))
        off = i * chunk
        in_chunk = (lab >= off) & (lab < off + chunk)
        idx = jnp.clip(lab - off, 0, chunk - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
        ll = ll + jnp.where(in_chunk, picked, 0.0)
        return (m_new, s, ll), None

    init = (jnp.full((n,), -1e30, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, ll), _ = lax.scan(jax.checkpoint(body), init,
                             (jnp.arange(n_chunks), wch))
    lse = m + jnp.log(s)
    return (lse - ll).reshape(orig_shape)


# ---------------------------------------------------------------------------
# Autoregressive generation: the prefill/decode pair over a slot-indexed KV
# cache (the model layer under horovod_tpu.serve.generate's continuous-
# batching engine; the paged block-table variants live in kv_blocks.py and
# share these helpers). Pure functions of (params, cache) — the cache is a
# plain pytree so it jits, donates, and shards like any other state. Unlike
# the training forward these run OUTSIDE shard_map: params placed with
# ``param_specs`` NamedShardings partition the matmuls under GSPMD, and
# ``kv_cache_specs`` shards the cache's head axis over ``tp`` to match the
# column-parallel wqkv layout (a tp column-slice holds whole heads).
# Dense models only (n_experts=0); sequence parallelism does not apply to
# single-token decode.
# ---------------------------------------------------------------------------


def _gen_weights(params):
    """Generation-path view of ``params``: int8-quantized leaves (the
    ``restore_for_inference(dtype="int8")`` wire format) dequantize here,
    INSIDE the jitted forward — weights stay int8 in HBM and XLA fuses the
    per-channel scale multiply into the consuming matmul."""
    from ..ops.quant import dequantize_tree
    return dequantize_tree(params)


def _check_dense(cfg: TransformerConfig, what: str):
    if cfg.n_experts:
        raise NotImplementedError(
            f"{what} supports dense FFNs only (cfg.n_experts="
            f"{cfg.n_experts}); the MoE dispatch has no incremental-decode "
            f"path yet")
    if _keys_split(cfg) != (4, 6):     # the dense block's keys alone
        raise NotImplementedError(
            f"{what} runs the dense block only (packed wqkv, GELU, tied "
            f"head): grouped key/value heads, q/k norm, RoPE, a gated "
            f"feed-forward, an untied head, sparse attention, layers of "
            f"several kinds (a 'gdn' or 'kda' layer's state is no "
            f"key/value cache, and an 'mla' layer's latent has no cache "
            f"of its own yet), leading dense layers, (1 + w) norms, an "
            f"output gate and a shared expert exist on the training path "
            f"alone")


def init_kv_cache(cfg: TransformerConfig, max_slots: int, max_len: int,
                  dtype: Any = None) -> Dict:
    """Fresh per-layer K/V cache for ``max_slots`` concurrent sequences of
    up to ``max_len`` tokens (prompt + generated).

    Returns ``{"k", "v": [n_layers, max_slots, max_len, n_heads, d_head],
    "lengths": [max_slots] int32}`` — ``lengths[s]`` is how many positions
    of slot ``s`` hold real K/V. Rows beyond a slot's length are garbage by
    contract (padded prefill writes land there) and are masked out of every
    attention; a slot's row is rewritten by the next ``prefill`` into it,
    so slots recycle without clearing.

    This is the CONTIGUOUS layout: every slot reserves ``max_len`` rows
    up front, so concurrent capacity is bounded by worst-case sequence
    length. :mod:`.kv_blocks` holds the paged sibling (fixed-size block
    pool + per-slot block tables, bit-identical streams) for workloads
    where typical requests run far short of ``max_len``."""
    _check_dense(cfg, "init_kv_cache")
    d_head = cfg.d_model // cfg.n_heads
    shape = (cfg.n_layers, max_slots, max_len, cfg.n_heads, d_head)
    kv_dtype = cfg.dtype if dtype is None else dtype
    return {"k": jnp.zeros(shape, kv_dtype),
            "v": jnp.zeros(shape, kv_dtype),
            "lengths": jnp.zeros((max_slots,), jnp.int32)}


def kv_cache_specs(cfg: TransformerConfig, mesh: Mesh) -> Dict:
    """PartitionSpec tree matching :func:`init_kv_cache`: the head axis
    shards over ``tp`` (mirroring ``param_specs``' column-parallel wqkv —
    each tp rank caches exactly the heads it computes); slots and
    positions stay replicated."""
    tp = "tp" if "tp" in _axes(mesh) else None
    kv = P(None, None, None, tp, None)
    return {"k": kv, "v": kv, "lengths": P()}


def _no_delta(li, name, x, y):
    """Default adapter hook: the base matmul output passes through
    untouched (see :mod:`.lora` for the LoRA delta callbacks)."""
    return y


def _prompt_forward(params, tokens, cfg: TransformerConfig, store_kv,
                    delta=None, attend=None):
    """Shared prompt-phase forward for the contiguous and paged prefills
    (``params`` already through :func:`_gen_weights`): per layer the
    computed K/V is handed to ``store_kv(li, k, v)`` (k/v
    ``[T, n_heads, d_head]``) — the ONLY layout-specific piece — and the
    attention is the same self-contained ``flash_attention`` either way,
    so both layouts' prefill logits are bitwise identical by
    construction (the cross-layout contract tests/test_paged_kv.py
    pins). ``delta(li, name, x, y)`` adjusts each target matmul's output
    (the LoRA hook; the default passes ``y`` through bit-unchanged).
    ``attend(li, q)`` replaces the self-contained causal attention with
    a caller-supplied read (q ``[1, T, n_heads, d_head]`` → attn of the
    same shape) — the chunked-prefill hook: ``store_kv`` runs FIRST, so
    the hook may gather the just-stored rows back out of a paged pool
    and attend across an arbitrary prefix span. Returns logits
    ``[T, vocab]`` f32."""
    from ..ops.pallas_attention import flash_attention
    dl = _no_delta if delta is None else delta
    T = tokens.shape[0]
    d_head = cfg.d_model // cfg.n_heads
    x = params["embed"][tokens][None].astype(cfg.dtype)     # [1, T, D]
    for li, layer in enumerate(params["layers"]):
        h = _rms_norm(x, layer["ln1"])
        qkv = dl(li, "wqkv", h, h @ layer["wqkv"].astype(cfg.dtype))
        qkv = qkv.reshape(1, T, cfg.n_heads, 3, d_head)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        store_kv(li, k[0], v[0])
        if attend is not None:
            attn = attend(li, q).astype(cfg.dtype)
        else:
            attn = flash_attention(
                q, k, v, causal=True,
                backend=cfg.attn_backend).astype(cfg.dtype)
        a_flat = attn.reshape(1, T, cfg.n_heads * d_head)
        x = x + dl(li, "wo", a_flat,
                   a_flat @ layer["wo"].astype(cfg.dtype))
        h2 = _rms_norm(x, layer["ln2"])
        up = jax.nn.gelu(dl(li, "w1", h2,
                            h2 @ layer["w1"].astype(cfg.dtype)))
        x = x + dl(li, "w2", up, up @ layer["w2"].astype(cfg.dtype))
    x = _rms_norm(x, params["lnf"])
    return jnp.matmul(x.astype(cfg.unembed_dtype),
                      params["embed"].T.astype(cfg.unembed_dtype),
                      preferred_element_type=jnp.float32)[0]


def _step_forward(params, last_tokens, cfg: TransformerConfig, mix,
                  delta=None):
    """Shared decode-step forward (``params`` already through
    :func:`_gen_weights`): ``mix(li, q, k, v)`` does the layout-specific
    cache write + attention read (q/k/v ``[S, n_heads, d_head]`` → attn
    of the same shape); everything else — the layer math both
    bit-identity contracts ride on — exists exactly once.
    ``delta(li, name, x, y)`` adjusts each target matmul's output (the
    batched per-slot LoRA hook; row-independent by construction, so the
    alone-vs-mixed bit-identity survives it). Returns logits
    ``[S, vocab]`` f32."""
    dl = _no_delta if delta is None else delta
    S = last_tokens.shape[0]
    d_head = cfg.d_model // cfg.n_heads
    x = params["embed"][last_tokens].astype(cfg.dtype)      # [S, D]
    for li, layer in enumerate(params["layers"]):
        h = _rms_norm(x, layer["ln1"])
        qkv = dl(li, "wqkv", h, h @ layer["wqkv"].astype(cfg.dtype)
                 ).reshape(S, cfg.n_heads, 3, d_head)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        attn = mix(li, q, k, v)
        a_flat = attn.reshape(S, cfg.n_heads * d_head)
        x = x + dl(li, "wo", a_flat,
                   a_flat @ layer["wo"].astype(cfg.dtype))
        h2 = _rms_norm(x, layer["ln2"])
        up = jax.nn.gelu(dl(li, "w1", h2,
                            h2 @ layer["w1"].astype(cfg.dtype)))
        x = x + dl(li, "w2", up, up @ layer["w2"].astype(cfg.dtype))
    x = _rms_norm(x, params["lnf"])
    return jnp.matmul(x.astype(cfg.unembed_dtype),
                      params["embed"].T.astype(cfg.unembed_dtype),
                      preferred_element_type=jnp.float32)


def prefill(params, tokens, cache: Dict, slot, cfg: TransformerConfig,
            length=None, *, adapters=None, adapter_idx=None,
            lora=None) -> Tuple[Dict, Any]:
    """Run the full prompt through the model, writing every position's K/V
    into ``cache`` at ``slot``.

    Args:
      tokens: [T] int32 prompt, optionally padded (``T`` is the compiled
        bucket; any pad token id works — padded positions' K/V are written
        but masked by ``length`` until real decode steps overwrite them).
      slot: int32 scalar — which cache row to fill (traced, so one
        compiled program serves every slot).
      length: true prompt length (int32 scalar; defaults to ``T``).
      adapters: optional stacked LoRA table (:mod:`.lora`); with it,
        ``adapter_idx`` (int32 scalar, ``-1``/None = base) picks the
        tenant's delta — data, not a compile key, so one compiled
        program serves every tenant.
      lora: the :class:`~.lora.LoraConfig` the table was built with
        (required with ``adapters``).

    Returns ``(cache', logits [T, vocab] f32)`` — logits at EVERY prompt
    position, matching one-shot :func:`forward` (the parity contract
    tests/test_generate.py pins); sampling reads row ``length - 1``.
    Reads nothing from ``cache`` rows, so a prefill's logits are
    independent of what other slots hold (the continuous-batching
    invariance contract).
    """
    _check_dense(cfg, "prefill")
    from .lora import make_delta
    delta = make_delta("prompt", adapters,
                       -1 if adapter_idx is None else adapter_idx,
                       lora, cfg)
    params = _gen_weights(params)
    T = tokens.shape[0]
    if T > cache["k"].shape[2]:
        raise ValueError(
            f"prompt bucket {T} exceeds the cache max_len "
            f"{cache['k'].shape[2]}")
    length = jnp.asarray(T if length is None else length, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    k_cache, v_cache = cache["k"], cache["v"]
    zero = jnp.zeros((), jnp.int32)       # x64 mode: indices must agree

    def store(li, k, v):
        nonlocal k_cache, v_cache
        idx = (jnp.asarray(li, jnp.int32), slot, zero, zero, zero)
        k_cache = lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype)[None, None], idx)
        v_cache = lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype)[None, None], idx)

    logits = _prompt_forward(params, tokens, cfg, store, delta=delta)
    lengths = cache["lengths"].at[slot].set(length)
    return {"k": k_cache, "v": v_cache, "lengths": lengths}, logits


def _cached_attention(q, k_cache, v_cache, positions):
    """One query token per slot against that slot's cache row: q [S, H, d],
    k/v_cache [S, M, H, d], positions [S] (index of the just-written
    token; attends 0..position inclusive). Same numerics as the training
    attention (f32 scores, 1/sqrt(d) scale, -1e30 mask, f32 softmax and
    value matmul, cast back) — the prefill/decode parity depends on it."""
    d = q.shape[-1]
    s = jnp.einsum("shd,smhd->shm", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * (float(d) ** -0.5)
    m = jnp.arange(k_cache.shape[1], dtype=jnp.int32)
    s = jnp.where(m[None, None, :] <= positions[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shm,smhd->shd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_step(params, last_tokens, cache: Dict, positions,
                cfg: TransformerConfig, *, adapters=None,
                adapter_idx=None, lora=None) -> Tuple[Dict, Any]:
    """One autoregressive step for every slot at once: embed each slot's
    last sampled token, write its K/V at ``positions[s]``, attend over the
    slot's cache (masked to ``<= positions[s]``), and return next-token
    logits.

    Args:
      last_tokens: [S] int32 — per-slot previous token (S = max_slots; the
        shape is FIXED, which is what makes continuous batching work: one
        compiled program regardless of occupancy).
      positions: [S] int32 — per-slot write index (== current length);
        ``-1`` marks an inactive slot, whose output row is garbage to be
        ignored (its scratch write lands at index 0 of a row that the next
        prefill into that slot rewrites before it is ever read).
      adapters: optional stacked LoRA table (:mod:`.lora`); with it,
        ``adapter_idx`` ([S] int32, ``-1`` = base row; None = all base)
        gathers each slot's delta — a mixed-adapter batch stays THIS one
        compiled program.
      lora: the :class:`~.lora.LoraConfig` the table was built with
        (required with ``adapters``).

    Returns ``(cache', logits [S, vocab] f32)``. Every per-slot row of the
    computation depends only on that slot's token, position, cache row and
    adapter row, so a request's token stream is bit-identical whether it
    decodes alone or alongside a full batch (the invariance
    tests/test_generate.py and tests/test_adapters.py pin).
    """
    _check_dense(cfg, "decode_step")
    S = last_tokens.shape[0]
    from .lora import make_delta
    delta = make_delta(
        "step", adapters,
        jnp.full((S,), -1, jnp.int32) if adapter_idx is None
        else adapter_idx, lora, cfg)
    params = _gen_weights(params)
    active = positions >= 0
    pos = jnp.where(active, positions, 0).astype(jnp.int32)
    rows = jnp.arange(S, dtype=jnp.int32)
    k_cache, v_cache = cache["k"], cache["v"]

    def mix(li, q, k, v):
        nonlocal k_cache, v_cache
        k_cache = k_cache.at[li, rows, pos].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[li, rows, pos].set(v.astype(v_cache.dtype))
        return _cached_attention(q, k_cache[li], v_cache[li], pos)

    logits = _step_forward(params, last_tokens, cfg, mix, delta=delta)
    lengths = jnp.where(active, pos + 1, cache["lengths"]
                        ).astype(jnp.int32)
    return {"k": k_cache, "v": v_cache, "lengths": lengths}, logits


def verify_step(params, draft_tokens, cache: Dict, positions,
                cfg: TransformerConfig, *, adapters=None,
                adapter_idx=None, lora=None) -> Tuple[Dict, Any]:
    """Speculative-decoding verify pass: score ``W = k + 1`` positions
    per slot in ONE forward.

    Args:
      draft_tokens: [S, W] int32 — per slot, column 0 is the slot's last
        sampled token (what ``decode_step`` would consume) and columns
        1..k its drafted continuation; unused tail columns are padding
        (any valid token id — their rows are never read by the host).
      positions: [S] int32 as in :func:`decode_step` (``-1`` inactive).

    Each column ``j`` writes its K/V at ``positions[s] + j`` and attends
    the slot's cache masked to ``<= positions[s] + j`` — writes landing
    at/past ``max_len`` are DROPPED by the scatter (out-of-bounds
    updates), so padded tail columns near the cache edge can never
    corrupt live rows.

    Returns ``(cache', logits [S, W, vocab] f32)`` — row ``j`` is the
    next-token distribution after consuming ``draft_tokens[s, :j + 1]``.

    Bit-identity contract: the ``W`` query columns are FLATTENED onto
    the slot axis, so every per-row matmul/norm and the cached
    attention run at exactly the decode-step shapes over exactly the
    per-row data sequential decode would see — logits row ``j`` and the
    K/V bytes written at ``positions[s] + j`` are bitwise identical to
    ``decode_step`` having consumed those tokens one at a time
    (tests/test_spec.py pins both). The cost is attention reading a
    ``W``-replicated cache view; a fused multi-query kernel is the
    hardware follow-up, gated behind this same signature.

    ``lengths`` bookkeeping is conservative under speculation: only
    column 0's position is claimed (the host decides the accepted count
    AFTER this program ran); the next step's write advances it past the
    accepted tokens. Rows between are written-but-unclaimed, which the
    "rows beyond lengths are garbage" contract already allows.
    """
    _check_dense(cfg, "verify_step")
    S, W = draft_tokens.shape
    from .lora import make_delta
    # Per-(slot, column) rows flatten to [S*W]; each column inherits its
    # slot's adapter row so the LoRA delta stays row-independent.
    aidx = (jnp.full((S,), -1, jnp.int32) if adapter_idx is None
            else adapter_idx)
    delta = make_delta("step", adapters, jnp.repeat(aidx, W), lora, cfg)
    params = _gen_weights(params)
    active = positions >= 0
    pos = jnp.where(active, positions, 0).astype(jnp.int32)
    rows = jnp.arange(S, dtype=jnp.int32)
    offs = jnp.arange(W, dtype=jnp.int32)   # x64 mode: indices must agree
    wpos = pos[:, None] + offs[None, :]                      # [S, W]
    flat_pos = wpos.reshape(S * W)
    k_cache, v_cache = cache["k"], cache["v"]

    def mix(li, q, k, v):
        nonlocal k_cache, v_cache
        k2 = k.reshape(S, W, k.shape[-2], k.shape[-1])
        v2 = v.reshape(S, W, v.shape[-2], v.shape[-1])
        k_cache = k_cache.at[li, rows[:, None], wpos].set(
            k2.astype(k_cache.dtype))
        v_cache = v_cache.at[li, rows[:, None], wpos].set(
            v2.astype(v_cache.dtype))
        # Each flat row (s, j) attends slot s's FULL cache row (with all
        # W fresh writes visible) under its own mask — the same [M] view
        # sequential decode at position pos+j reads.
        kg = jnp.repeat(k_cache[li], W, axis=0)
        vg = jnp.repeat(v_cache[li], W, axis=0)
        return _cached_attention(q, kg, vg, flat_pos)

    logits = _step_forward(params, draft_tokens.reshape(S * W), cfg, mix,
                           delta=delta)
    lengths = jnp.where(active, pos + 1, cache["lengths"]
                        ).astype(jnp.int32)
    return ({"k": k_cache, "v": v_cache, "lengths": lengths},
            logits.reshape(S, W, -1))


def make_parallel_train_step(cfg: TransformerConfig, mesh: Mesh,
                             optimizer: optax.GradientTransformation,
                             aux_weight: float = 0.01,
                             wire_dtype=None,
                             *,
                             zero: bool = False,
                             accum_steps: int = 1,
                             guard_nonfinite=None,
                             overlap=None,
                             fusion_threshold=None):
    """Build (init_state, step): the compiled multi-axis training step.

    ``init_state(rng)`` returns (params, opt_state) as global sharded
    arrays; ``step(params, opt_state, tokens, labels)`` runs one update and
    returns (params, opt_state, loss). tokens/labels are global
    [B, T] int32, sharded (dp, sp).

    This family is a THIN WRAPPER over the core stack (ISSUE 8): the loss
    is handed to ``training.make_train_step(mesh=, param_specs=)`` and
    everything below the loss — spec-grouped fused collectives,
    ``zero=True`` ZeRO-1 sharding of the optimizer state over ``dp``
    (tp-sharded params included), ``accum_steps`` microbatch scanning,
    the ``guard_nonfinite`` bad-step guard (default:
    ``HVD_GUARD_NONFINITE``), ``overlap`` emission and ``wire_dtype``
    reduced-precision wire — is the ONE implementation the flax plane
    runs; the duplicated grad-sync/update logic this file used to carry
    is gone. On a skipped (non-finite) step the returned loss is 0 and
    params/opt_state come back bit-unchanged.

    ``wire_dtype`` (``"bf16"``/``"fp8"``; see ``docs/performance.md``
    "Overlap & wire formats") runs the data-parallel gradient averages in
    reduced wire precision with fp32 scales and fp32 result accumulation.

    ``aux_weight`` weighs the expert layers' load-balancing loss in the
    loss. A Python number equal to 0 builds no balance loss at all: XLA
    does not fold ``0.0 * x`` of floats (x may be NaN), so a zero weight
    written into the loss would keep the loss's forward and pay the
    router's backward for a gradient of zeros. The step's losses and
    parameters are the same either way.

    ``overlap``: ``None`` (the default) and ``True`` reduce each layer's
    gradients INSIDE the backward wherever they cross chips (a sync axis
    with more than one member, ``zero=False``, ``accum_steps == 1``): one
    bucket a layer, issued when the layer's backward has produced it and
    due before the backward goes on below the layer underneath, so the
    all-reduce runs under that layer's backward
    (``ops/fusion.reduce_in_backward``; the layers must have the same
    leaves); the embedding, the head and the
    final norm follow after the backward. ``False`` keeps the plan that
    reduces everything after the backward. Where the in-backward plan
    does not apply, ``True`` is PR 6's barrier-chained emission after the
    backward, as on the flax plane.
    """
    from .. import training
    from ..ops.fusion import (backward_carry, plan_grad_sync,
                              reduce_in_backward)
    from ..optimizer import DistributedOptimizer

    axes = _axes(mesh)
    ep = mesh.shape.get("ep", 1)
    if cfg.n_experts and (_held(cfg) % ep or cfg.first_expert + _held(cfg)
                          > cfg.n_experts):
        raise ValueError(
            f"the {_held(cfg)} experts held (of n_experts={cfg.n_experts}, "
            f"from {cfg.first_expert}) must divide over the ep mesh axis "
            f"of size {ep} and lie among the router's")
    _check_pattern(cfg)
    _check_mesh(cfg, axes)
    # Batch dim shards over dp AND ep (GShard layout: ep ranks carry
    # distinct tokens; experts see everyone's via the all_to_all); sequence
    # dim over sp.
    batch_axes = tuple(a for a in ("dp", "ep") if a in axes)
    batch_spec = P(batch_axes if len(batch_axes) > 1
                   else (batch_axes[0] if batch_axes else None),
                   "sp" if "sp" in axes else None)
    specs = param_specs(cfg, mesh)

    # Whether the layers' buckets are reduced inside the backward (the
    # docstring's ``overlap``). With one member on every sync axis nothing
    # crosses chips and the step is the old one, op for op; ZeRO's
    # reduce-scatter and microbatch accumulation (one exchange per
    # accumulated step) keep the plan that reduces after the backward.
    # One carry serves every layer's bucket, so the layers must have the
    # same leaves (``ops/fusion.backward_carry``): a model of several kinds,
    # or with leading dense layers before expert layers, keeps the plan
    # that reduces after the backward.
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    layer_syncs = plan_grad_sync(
        jax.tree_util.tree_leaves(specs["layers"][:1], is_leaf=is_spec),
        mesh)
    in_backward = (overlap is not False and not zero and accum_steps == 1
                   and len({jax.tree_util.tree_structure(layer,
                                                         is_leaf=is_spec)
                            for layer in specs["layers"]}) == 1
                   and any(mesh.shape[a] > 1
                           for s in layer_syncs for a in s.psum))
    if in_backward:
        overlap = False     # what is left after the backward needs no order

    dist_opt = DistributedOptimizer(
        optimizer, zero=zero, wire_dtype=wire_dtype, overlap=overlap,
        fusion_threshold=fusion_threshold, mesh=mesh, param_specs=specs)

    def _bucket(k):
        # Buckets are numbered in the order the backward issues them: the
        # highest layer's first.
        return cfg.n_layers - 1 - k

    def _grad_sync(k, layer, x, carry):
        if carry is None:
            carry = backward_carry(layer, layer_syncs)
        return reduce_in_backward(layer, x, carry, layer_syncs, _bucket(k),
                                  wire=dist_opt.update.wire_dtype)

    # The docstring's ``aux_weight``: a static zero builds no balance loss.
    with_balance = not (isinstance(aux_weight, (int, float))
                        and aux_weight == 0)

    def _loss_fn(params, tokens, labels):
        x, per_layer = _forward_layers(
            params, tokens, cfg, mesh,
            grad_sync=_grad_sync if in_backward else None)
        if cfg.loss_chunk:
            nll = chunked_nll(x, _unembedding(params, cfg), labels, cfg)
        else:
            nll = dense_nll(_logits(x, params, cfg), labels)
        loss = jnp.mean(nll)
        if any("aux" in e for e in per_layer):
            _balance_counter().labels(
                built="yes" if with_balance else "no").inc()
        if with_balance:
            loss = loss + aux_weight * _aux_total(per_layer)
        if cfg.indexer:
            # Mean over layers and rows of the indexer's KL: it trains the
            # indexer alone, the NLL everything else (ops/sparse_attention).
            loss = loss + jnp.sum(
                jnp.stack([e["kl_sum"] for e in per_layer])
            ) / (len(per_layer) * tokens.size)
        return loss

    def _vag(params, batch_stats, tokens, labels, rng):
        # The core step's value_and_grad contract; the transformer has no
        # batch statistics and owns its remat (cfg.remat) and rng-free
        # forward, so stats/logits ride as None.
        def lf(p):
            # The same scope as training._build_value_and_grad's loss:
            # a device trace splits the step by it.
            with jax.named_scope("forward"):
                return _loss_fn(p, tokens, labels), (None, None)
        return jax.value_and_grad(lf, has_aux=True)(params)

    if in_backward:
        # Which bucket of the backward reduced each leaf, -1 for none: the
        # optimizer reduces only what is left.
        _vag.presynced = {
            **{name: -1 for name in specs if name != "layers"},
            "layers": [jax.tree_util.tree_map(
                lambda _, k=k: _bucket(k), layer,
                is_leaf=lambda x: isinstance(x, P))
                for k, layer in enumerate(specs["layers"])]}

    core = training.make_train_step(
        None, dist_opt, mesh=mesh, param_specs=specs,
        batch_spec=batch_spec, donate=False, accum_steps=accum_steps,
        guard_nonfinite=guard_nonfinite, overlap=overlap,
        _value_and_grad=_vag)

    def init_state(rng):
        params = init_params(rng, cfg)
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
        return params, dist_opt.init(params)

    def _state(params, opt_state):
        return training.TrainState(step=jnp.zeros((), jnp.int32),
                                   params=params, opt_state=opt_state,
                                   batch_stats=None)

    def step(params, opt_state, tokens, labels):
        st, metrics = core(_state(params, opt_state), (tokens, labels))
        return st.params, st.opt_state, metrics["loss"]

    # AOT handle (jax .lower convention) for HLO-pinned tests.
    step.lower = lambda params, opt_state, tokens, labels: core.lower(
        _state(params, opt_state), (tokens, labels))
    return init_state, step
