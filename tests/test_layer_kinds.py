"""Every layer kind of ``parallel/transformer.py`` at a toy size on the CPU,
with and without experts where the kind takes them: the packed dense block,
projected attention with the indexer, "swa", "gdn", "kda" and "mla". For
each, the parameters a seed draws (sha256 of every leaf, by path, dtype,
shape and bytes), ``param_specs`` in the tree structure ``init_params``
draws, and the set of ``named_scope`` strings in the lowered training step
(``as_text`` leaves them out; the per-layer readers under
``benchmarks/layer_metrics/`` find device time by them), each as the
module drew and lowered them before its kinds became one table."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.transformer import (
    GatedDeltaNet, Indexer, KimiDeltaAttention, LatentAttention,
    SlidingWindow, TransformerConfig, init_params, make_parallel_train_step,
    param_specs)

F32 = jnp.float32
B, T = 2, 32

_EXPERTS = dict(n_experts=4, moe_top_k=2, moe_renormalize=True)
# The expert layers the later kinds came with: a leading dense layer, a
# shared expert, sigmoid scores with a selection bias and a scale.
_EXPERTS_WIDE = dict(_EXPERTS, dense_layers=1, dense_ff=48,
                     shared_expert_ff=32, moe_score="sigmoid",
                     moe_select_bias=True, moe_scale=2.5, norm_eps=1e-5)
_PROJECTED = dict(n_kv_heads=2, d_head=8, qk_norm=True, rope_theta=1e4,
                  mlp="swiglu", tied_head=False)

CASES = {
    "dense": dict(),
    "dense-experts": dict(_EXPERTS),
    "attn-indexer": dict(_PROJECTED, indexer=Indexer(2, 8, 8)),
    "attn-indexer-experts": dict(_PROJECTED, indexer=Indexer(2, 8, 8),
                                 **_EXPERTS),
    "swa": dict(_PROJECTED, layer_pattern=("swa", "attn"), rope_theta=0.0,
                swa=SlidingWindow(8, rope_theta=1e4), attn_gate=True,
                post_norms=True, norm_offset=True, embed_scale=2.0),
    "swa-experts": dict(_PROJECTED, layer_pattern=("swa", "attn"),
                        rope_theta=0.0, swa=SlidingWindow(8, rope_theta=1e4),
                        attn_gate=True, post_norms=True, norm_offset=True,
                        embed_scale=2.0, shared_expert_ff=32, **_EXPERTS),
    "gdn": dict(_PROJECTED, layer_pattern=("gdn", "attn"), attn_gate=True,
                gdn=GatedDeltaNet(2, 4, 8, 8, chunk=16, backend="xla")),
    "gdn-experts": dict(_PROJECTED, layer_pattern=("gdn", "attn"),
                        attn_gate=True, shared_expert_ff=32,
                        gdn=GatedDeltaNet(2, 4, 8, 8, chunk=16,
                                          backend="xla"), **_EXPERTS),
    "kda": dict(_PROJECTED, layer_pattern=("kda", "attn"),
                kda=KimiDeltaAttention(2, 8, chunk=16, backend="xla")),
    "kda-experts": dict(_PROJECTED, layer_pattern=("kda", "attn"),
                        kda=KimiDeltaAttention(2, 8, chunk=16,
                                               backend="xla"),
                        **_EXPERTS_WIDE),
    "mla": dict(mlp="swiglu", tied_head=False, layer_pattern=("mla",),
                mla=LatentAttention(12, 8, 8, 8, rope_theta=1e4)),
    "mla-experts": dict(mlp="swiglu", tied_head=False, layer_pattern=("mla",),
                        mla=LatentAttention(12, 8, 8, 8, rope_theta=1e4),
                        shared_expert_gate=False, **_EXPERTS_WIDE),
}

# The mesh each case is lowered on: tensor parallelism where the kind
# takes it, an expert-parallel pair beside experts.
MESHES = {"dense": ("dp", "tp"), "attn-indexer": ("dp",)}

PARAMS_SHA = {
    "dense":
        "7eedcf5c881494f71cf674660ab271e5ab90b7bdd331ec020149a2a8c713dc78",
    "dense-experts":
        "5b1cb23c9b9549673a03858f4cb37a9fbed6d67579abc7746b103f0b796d9818",
    "attn-indexer":
        "784f6530deaa4db335d9ef97f7173ba97c47eb2ec942059d82b93bd5ced1abba",
    "attn-indexer-experts":
        "bf60f1128726db7ad1aeb34ce0cc9cc011fae848a72f6330566d797882a4e291",
    "swa":
        "a2f0afa473c3a802ab3c55f58cc9bd8218ed326c92fafce1e61ce82858b7aa4c",
    "swa-experts":
        "2972944a8665d91cbf68643111d5f35cb01f8a713879771f46113c855a5ed08a",
    "gdn":
        "1dc5577caa38d7ee19ffb0a5c105850417d61cf7ae54cad43c5c7f9bd483e140",
    "gdn-experts":
        "4f2fa7dbe09aeb8e11595b1baaac5e1abbad6058258b563894753e5e47712147",
    "kda":
        "8cebc62e5ddc82b01647e32a1a3f11d3506a55226aab196dc0dedaafad38f59d",
    "kda-experts":
        "8c1f6f7cccf489a530a15a3272d4fd2319a64cc6f51630aa7b2f8182a6ec8afd",
    "mla":
        "8a0e55c653f48b1f739ab1f812d41347d46101f44f644a29348f08c7b3f3dbc1",
    "mla-experts":
        "0a94181fa0e3dacdbc27b55cff67c708863acc844abfe09d3af5b471b17a22a1",
}

SCOPES = {
    "dense": [],
    "dense-experts": ["moe.experts", "moe.route"],
    "attn-indexer": [
        "attn.indexer", "attn.indexer_loss", "attn.select", "attn.sparse"],
    "attn-indexer-experts": [
        "attn.indexer", "attn.indexer_loss", "attn.select", "attn.sparse",
        "moe.experts", "moe.route"],
    "swa": ["attn.full", "attn.swa"],
    "swa-experts": [
        "attn.full", "attn.swa", "moe.experts", "moe.route", "moe.shared"],
    "gdn": ["attn.full", "gdn.conv", "gdn.out", "gdn.proj", "gdn.scan"],
    "gdn-experts": [
        "attn.full", "gdn.conv", "gdn.out", "gdn.proj", "gdn.scan",
        "moe.experts", "moe.route", "moe.shared"],
    "kda": ["attn.full", "kda.conv", "kda.out", "kda.proj", "kda.scan"],
    "kda-experts": [
        "attn.full", "ffn.dense", "kda.conv", "kda.out", "kda.proj",
        "kda.scan", "moe.experts", "moe.route", "moe.shared"],
    "mla": ["attn.mla", "mla.rope"],
    "mla-experts": [
        "attn.mla", "ffn.dense", "mla.rope", "moe.experts", "moe.route",
        "moe.shared"],
}


def _cfg(case):
    return TransformerConfig(**{
        **dict(vocab=64, d_model=32, n_heads=4, n_layers=3, d_ff=32,
               dtype=F32, attn_backend="xla", unembed_dtype=F32),
        **CASES[case]})


def _mesh(case):
    axes = MESHES.get(case, ("dp", "ep") if "experts" in case else ("dp",))
    shape = (1,) * (len(axes) - 1) + (2,) if len(axes) > 1 else (1,)
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


def _params_sha(cfg):
    h = hashlib.sha256()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        init_params(jax.random.PRNGKey(0), cfg))
    for path, leaf in flat:
        leaf = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {leaf.dtype} "
                 f"{leaf.shape}".encode())
        h.update(leaf.tobytes())
    return h.hexdigest()


_SCOPE = re.compile(r'(?<=[/"])((?:attn|gdn|kda|mla|ffn|moe)\.[a-z_]+)(?=/)')


def _scopes(cfg, mesh):
    init_state, step = make_parallel_train_step(cfg, mesh, optax.sgd(0.1))
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, T), jnp.int32)
    text = step.lower(params, opt_state, tokens, tokens).as_text(
        debug_info=True)
    return sorted(set(_SCOPE.findall(text)))


@pytest.mark.parametrize("case", list(CASES))
def test_layer_kind_draws_shards_and_lowers_as_it_did(case):
    cfg, mesh = _cfg(case), _mesh(case)
    assert _params_sha(cfg) == PARAMS_SHA[case]
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    assert (jax.tree_util.tree_structure(param_specs(cfg, mesh),
                                         is_leaf=is_spec)
            == jax.tree_util.tree_structure(jax.eval_shape(
                lambda key: init_params(key, cfg), jax.random.PRNGKey(0))))
    assert _scopes(cfg, mesh) == SCOPES[case]
