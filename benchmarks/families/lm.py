"""Family ``lm``: the repo's dense decoder (``parallel/transformer.py``)
through ``make_parallel_train_step``, built the way ``chip_smoke._lm_train``
builds it. The configuration's file names the sizes with the source's
(Hugging Face) keys; its ``training`` group holds what the source does not
say."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from reference import lm as reference

RATE_METRIC = "tokens_per_s_per_chip"
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# The system's training forward (bf16 activations, flash kernel, bf16
# unembedding with f32 accumulation) against the float32 reference, per
# token over 2 sequences. Measured on the chip (my chip runs, PR 23, four
# seeds): mean |difference| of the per-token loss 0.0062-0.0063 (largest
# single token 0.040), the two mean losses at most 3.4e-4 apart. The bounds
# sit 2.4x and 6x above that. They are far below what a lower precision
# gives: 8-bit activations (4 significant bits fewer than bf16's 8) multiply
# the per-token error by about 16, and a wrong block (a missing norm,
# another GELU, an unscaled softmax) moves the mean loss by more than 0.1.
TOL_MEAN_ABS_TOKEN = 0.015
TOL_MEAN_LOSS = 0.002


def hlo_counts(compiled) -> dict:
    """Pallas kernels and all-reduces in a compiled program's text."""
    hlo = compiled.as_text()
    return {"tpu_custom_call": hlo.count("tpu_custom_call"),
            "all-reduce": hlo.count("all-reduce(")
            + hlo.count("all-reduce-start(")}


class Family:
    def __init__(self, ctx):
        from horovod_tpu.parallel.mesh import create_hybrid_mesh
        from horovod_tpu.parallel.transformer import (
            TransformerConfig, make_parallel_train_step)
        c, t, tr = ctx.config, ctx.traffic, ctx.config["training"]
        self.ctx = ctx
        self.mesh = create_hybrid_mesh(devices=ctx.devices,
                                       **t.get("mesh", {"dp": ctx.chips}))
        self.cfg = TransformerConfig(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
            d_ff=c["intermediate_size"], dtype=_DTYPES[tr["activation_dtype"]],
            attn_backend=tr["attn_backend"],
            unembed_dtype=_DTYPES[tr["unembed_dtype"]],
            remat=tr["remat"], loss_chunk=tr["loss_chunk"])
        o = tr["optimizer"]
        self.init_state, self._step = make_parallel_train_step(
            self.cfg, self.mesh, optax.adamw(
                o["lr"], b1=o["b1"], b2=o["b2"],
                weight_decay=o["weight_decay"]))
        self.batch = t["batch_per_chip"] * ctx.chips
        self.seq_len = t["seq_len"]
        self.units_per_step = self.batch * self.seq_len
        self.batch_sharding = NamedSharding(self.mesh, P("dp", None))
        self.compiled = None

    # -- inputs and state, from the seed --------------------------------

    def make_pool(self, n: int):
        """n host batches of (tokens, labels), labels the next token."""
        rng = np.random.default_rng(self.ctx.seed)
        pool = []
        for _ in range(n):
            tok = rng.integers(0, self.cfg.vocab,
                               size=(self.batch, self.seq_len + 1),
                               dtype=np.int32)
            pool.append((np.ascontiguousarray(tok[:, :-1]),
                         np.ascontiguousarray(tok[:, 1:])))
        return pool

    def place(self, batch):
        return tuple(jax.device_put(x, self.batch_sharding) for x in batch)

    def init(self):
        """Weights and optimizer state made on the device(s) in one jitted
        call from the seed."""
        key = jax.random.PRNGKey(self.ctx.seed % (2 ** 31))
        with self.ctx.compiling("init_state"):
            return jax.block_until_ready(jax.jit(self.init_state)(key))

    # -- the step ---------------------------------------------------------

    def lower(self, state, batch):
        """The donated step, lowered for ``state`` and ``batch`` (arrays, or
        shapes with shardings: ``compile_rehearsal.py``)."""
        def update(p, o, tok, lab):
            return self._step(p, o, tok, lab)
        return jax.jit(update, donate_argnums=(0, 1)).lower(*state, *batch)

    def compile(self, state, batch):
        with self.ctx.compiling("train_step"):
            self.compiled = self.lower(state, batch).compile()
        counts = hlo_counts(self.compiled)
        self.ctx.log(event="compiled_step", **counts)
        if jax.devices()[0].platform == "tpu" \
                and self.cfg.attn_backend == "pallas" \
                and not counts["tpu_custom_call"]:
            raise RuntimeError("no tpu_custom_call in the compiled LM step: "
                               "the flash kernel is not in it")

    def step(self, state, batch):
        params, opt_state, loss = self.compiled(*state, *batch)
        return (params, opt_state), loss

    # -- correctness --------------------------------------------------------

    def reference_check(self, state) -> bool:
        """The system's training forward against the plain reference on 2
        seeded sequences, per token."""
        from horovod_tpu.parallel.transformer import dense_nll, forward
        params = state[0]
        rng = np.random.default_rng(self.ctx.seed + 1)
        n = self.ctx.traffic.get("reference_sequences", 2)
        tok = rng.integers(0, self.cfg.vocab, size=(n, self.seq_len + 1),
                           dtype=np.int32)
        tokens, labels = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])

        def system(p, t, l):
            return dense_nll(forward(p, t, self.cfg, self.mesh)[0], l)

        def plain(p, t, l):
            return reference.token_nll(p, t, l, self.cfg.n_heads)

        with self.ctx.compiling("reference_check"):
            got = np.asarray(jax.jit(system)(params, tokens, labels))
            want = np.asarray(jax.jit(plain)(params, tokens, labels))
        token_err = float(np.mean(np.abs(got - want)))
        loss_err = float(abs(got.mean() - want.mean()))
        ok = bool(np.all(np.isfinite(got))
                  and token_err <= TOL_MEAN_ABS_TOKEN
                  and loss_err <= TOL_MEAN_LOSS)
        self.ctx.log(event="reference_check", ok=ok,
                     system_loss=float(got.mean()),
                     reference_loss=float(want.mean()),
                     mean_abs_token_err=token_err,
                     max_abs_token_err=float(np.max(np.abs(got - want))),
                     tol_mean_abs_token=TOL_MEAN_ABS_TOKEN,
                     tol_mean_loss=TOL_MEAN_LOSS)
        return ok

    def replicas_equal(self, state) -> bool:
        """Every replica holds the same parameters, bit for bit: a wrapping
        uint32 sum of each leaf's bits, computed by each device on its own
        copy, compared across devices."""
        def bits(p):
            return sum(jnp.sum(jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32))
                for x in jax.tree_util.tree_leaves(p))
        total = jax.jit(bits)(state[0])
        sums = [int(np.asarray(s.data)) for s in total.addressable_shards]
        self.ctx.log(event="replica_checksums", sums=sums)
        return len(sums) == self.ctx.chips and len(set(sums)) == 1


def build(ctx) -> Family:
    return Family(ctx)
