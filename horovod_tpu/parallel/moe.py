"""The expert layer: a top-k mixture of feed-forward experts of which each
chip HOLDS some, routes over all, and drops nothing.

Every token's router scores all ``E`` experts (a softmax over them, or a
sigmoid of each logit) and keeps its ``top_k`` (of the scores, or of the
scores plus a bias a expert that counts for the selection alone; weights
renormalised over the kept ones, or the raw scores; times a scaling
factor). A chip
is told which experts it holds — the ``held`` consecutive experts from
``first_expert``, whose weights are the leading dimension of ``w_up`` /
``w_down`` (/ ``w_gate``) — and computes the part of the layer's output
that THOSE experts give, for every assignment that fell to them: rows are
grouped by expert and multiplied by grouped matrix products (on the TPU
the Pallas kernel that ships with jax, ``lax.ragged_dot`` elsewhere),
whatever the imbalance; there is no capacity and no
dropped token. What experts held elsewhere would add is added elsewhere:

* no ``axis_name`` (or an axis of size 1): the chip's share is the result.
  A chip that stands for one member of a larger expert-parallel group
  (``first_expert``/``held`` a slice of ``E``) runs exactly this, with no
  exchange and nothing standing in for the absent members. What needs the
  absent members is left out: their experts' outputs, and the gradient of
  the routing weights (``moe_ffn``), so the router is not trained there;
* ``axis_name`` of size > 1: rank r holds experts ``[first_expert + r *
  held, ... + held)``. Tokens and their routing are all-gathered over the
  axis, every rank computes its experts' share for all of them, and a
  reduce-scatter sums the shares back to the tokens' owners.

The expert plane is a mesh axis like any other: expert weights carry ``ep``
in their PartitionSpecs (``parallel/transformer.py``) and their gradients
ride the same spec-grouped collective plan as every other leaf
(``ops/fusion.plan_grad_sync``).

Named scopes (a device trace splits the step by them): ``moe.route`` and
``moe.experts``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.registry import registry as _registry


def _tile(n: int, most: int) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``n``, or 0."""
    return next((t for t in range(most, 0, -128) if n % t == 0), 0)


_GMM_ROWS = 512      # rows of a grouped product's tile on the TPU


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _megablox():
    """The module of jax's Pallas grouped matrix products (the package
    rebinds its name ``gmm`` to a function)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _grouped_kernel(rows, w, sizes):
    """rows [m, k] grouped by ``sizes`` x w [groups, k, n] -> [m, n] in
    rows' dtype, float32 inside: the Pallas grouped matrix product that
    ships with jax (megablox), tiles chosen per product. It visits only the
    row tiles that hold a group's rows; rows of no group stay unwritten."""
    gmm = _megablox()
    (m, k), n = rows.shape, w.shape[2]
    return gmm.gmm(rows, w, sizes, rows.dtype,
                   (_GMM_ROWS, _tile(k, 1024), _tile(n, 1024)),
                   interpret=not _on_tpu())


def _grouped_kernel_fwd(rows, w, sizes):
    return _grouped_kernel(rows, w, sizes), (rows, w, sizes)


def _grouped_kernel_bwd(res, g):
    gmm = _megablox()
    rows, w, sizes = res
    (m, k), n = rows.shape, w.shape[2]
    d_rows = gmm.gmm(g, w, sizes, rows.dtype,
                     (_GMM_ROWS, _tile(n, 1024), _tile(k, 1024)),
                     transpose_rhs=True, interpret=not _on_tpu())
    d_w = gmm.tgmm(rows.swapaxes(0, 1), g, sizes, w.dtype,
                   (_GMM_ROWS, _tile(k, 1024), _tile(n, 1024)),
                   num_actual_groups=w.shape[0], interpret=not _on_tpu())
    return d_rows, d_w, None


_grouped_kernel.defvjp(_grouped_kernel_fwd, _grouped_kernel_bwd)


def _grouped(rows, w, sizes, real):
    """Each group's rows times its expert's matrix; rows of no group come
    out as zeros. On the TPU where the shapes tile, the Pallas kernel
    (measured on a v5e, PERF.md PR 28: XLA's own ``ragged_dot`` kernel took
    4 to 5 ms for [32768, 2048] x [16, 2048, 768] however few rows were
    real); ``lax.ragged_dot`` otherwise. Either leaves rows of no group
    unwritten, and whatever lies there must not meet arithmetic: 0 x NaN."""
    (m, k), n = rows.shape, w.shape[2]
    if (_on_tpu() and m % _GMM_ROWS == 0 and _tile(k, 1024)
            and _tile(n, 1024)):
        out = _grouped_kernel(rows, w, sizes)
    else:
        out = lax.ragged_dot(rows, w, sizes)
    return jnp.where(real, out, 0)


def _chunk(c, k, order, sizes, ends):
    """Chunk ``c`` of the sorted assignments: (the tokens of its rows
    [cap], ``through``). ``through(gathered, ws, weight_c)`` takes the
    tokens' rows of x through their experts ``ws`` = (w_gate or None,
    w_up, w_down), weighted: [cap, D] float32, zeros where a row is of no
    group."""
    cap = order.shape[1]
    lo = c * cap
    sizes_c = (jnp.clip(ends, lo, lo + cap)
               - jnp.clip(ends - sizes, lo, lo + cap))
    real = (lo + jnp.arange(cap, dtype=jnp.int32) < ends[-1])[:, None]

    def through(gathered, ws, weight_c):
        w_gate, w_up, w_down = ws
        rows = jnp.where(real, gathered, 0)
        up = _grouped(rows, w_up, sizes_c, real)
        if w_gate is None:
            h = jax.nn.gelu(up)
        else:
            h = jax.nn.silu(_grouped(rows, w_gate, sizes_c, real)) * up
        out = _grouped(h.astype(rows.dtype), w_down, sizes_c, real)
        return out.astype(jnp.float32) * weight_c[:, None]
    return order[c] // k, through


def _entered(ends, cap):
    """Chunks that hold a row (a device scalar): the loops' trip count."""
    return -(-ends[-1] // cap)


def _first_then_entered(add, acc, order, ends):
    """``add(c, acc)`` for chunk 0, straight, then for the chunks after it
    that hold a row, as a loop (none is written where one chunk holds
    every row)."""
    n_chunks, cap = order.shape
    acc = add(0, acc)
    if n_chunks > 1:
        acc = lax.fori_loop(1, _entered(ends, cap), add, acc)
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(k, x, ws, weight, order, sizes, ends):
    """Every chunk's rows through their experts, added to their tokens:
    y [M, D] float32. ``weight`` / ``order`` [n_chunks, cap] are the sorted
    assignments' weights and indices, ``sizes`` / ``ends`` [held] the
    groups.

    Chunk 0 is worked straight; chunks 1 .. n_entered - 1 are a loop whose
    TRIP COUNT is the load, in both directions. A chunk that holds no row
    is then nothing in the program: a ``lax.scan`` over all the chunks
    with the empty ones skipped by a ``cond`` still paid, in its
    transpose, zeros the size of x and of every weight written and added
    a skipped turn (measured on a v5e: PERF.md PR 32, 8.6 ms a turn and
    layer; PR 37, 3.9 in the Keye cell), and a ``cond`` around the turns
    after the first paid them once."""
    def add(c, y):
        token, through = _chunk(c, k, order, sizes, ends)
        return y.at[token].add(through(x[token], ws, weight[c]))
    return _first_then_entered(add, jnp.zeros(x.shape, jnp.float32), order,
                               ends)


def _chunks_fwd(k, x, ws, weight, order, sizes, ends):
    # What is saved does not grow with the chunks: the backward works a
    # chunk again from its indices.
    return (_chunks(k, x, ws, weight, order, sizes, ends),
            (x, ws, weight, order, sizes, ends))


def _chunks_bwd(k, res, g):
    """The cotangents of x (added up in float32, a token once for each of
    its assignments), of the weights and of the assignments' weights:
    chunk 0's own, then each entered chunk's ADDED in the loop's carry,
    which a while loop updates in place."""
    x, ws, weight, order, sizes, ends = res

    def add(c, acc):
        dx, dws, dweight = acc
        token, through = _chunk(c, k, order, sizes, ends)
        vjp = jax.vjp(through, x[token], ws, weight[c])[1]
        d_rows, d_ws, d_weight_c = vjp(g[token])
        return (dx.at[token].add(d_rows.astype(jnp.float32)),
                d_ws if dws is None else jax.tree_util.tree_map(
                    jnp.add, dws, d_ws),
                lax.dynamic_update_index_in_dim(dweight, d_weight_c, c, 0))
    dx, dws, dweight = _first_then_entered(
        add, (jnp.zeros(x.shape, jnp.float32), None, jnp.zeros_like(weight)),
        order, ends)
    return dx.astype(x.dtype), dws, dweight, None, None, None


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _held_experts(x, ids, gates, w_gate, w_up, w_down, first, n_experts):
    """The held experts' share of the output for tokens ``x`` [M, D] with
    routing ``ids`` / ``gates`` [M, k]: (y [M, D], assignments per held
    expert [held], chunks that held a row).

    Assignments are sorted by expert, absent experts last, and worked in
    chunks of half as many rows again as a balanced router would send here
    (one chunk when every expert is held): a balanced router's load varies
    by a tenth and more from batch to batch and layer to layer, and a chunk
    of exactly that load would be followed by a second, nearly empty one
    every other step, which costs the same rows. A shorter chunk pays more
    tiles (one more for each expert's boundary). A chunk gathers and scatters
    all its rows, the chunks past the last row held here are never entered
    (``_chunks``), and the grouped products visit only the tiles that hold
    a group's rows: the work follows the load the router sends, and a step
    takes longer when the held experts are popular.

    The bookkeeping has no scatter and no gather of single values, which
    XLA's TPU backend works a value at a time (on a v5e, 8.7 ns an
    assignment for a scatter-add count and 7.1 for a gather; PERF.md §5):
    ONE stable sort carries the weights into the assignments' order beside
    their indices, and each held expert's assignments are counted by a
    dense compare-and-sum."""
    M, D = x.shape
    k, held = ids.shape[1], w_up.shape[0]
    local = ids.reshape(-1) - first
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).astype(jnp.int32)
    _, order, weight = lax.sort(
        (group, lax.iota(jnp.int32, group.size), gates.reshape(-1)),
        num_keys=1, is_stable=True)
    sizes = jnp.sum(group == jnp.arange(held, dtype=jnp.int32)[:, None],
                    axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)

    n_rows = M * k
    unit = _GMM_ROWS if n_rows % _GMM_ROWS == 0 else 8
    cap = min(n_rows, max(unit, -(-n_rows * held * 3 // (n_experts * 2)
                                  // unit) * unit))
    n_chunks = -(-n_rows // cap)
    pad = n_chunks * cap - n_rows
    order = jnp.pad(order, (0, pad)).reshape(n_chunks, cap)
    weight = jnp.pad(weight, (0, pad)).reshape(n_chunks, cap)
    y = _chunks(k, x, (w_gate, w_up, w_down), weight, order, sizes, ends)
    return y.astype(x.dtype), sizes, _entered(ends, cap)


def moe_ffn(x, router_w, w_up, w_down, *, w_gate=None, top_k: int = 1,
            renormalize: bool = False, first_expert=0, axis_name=None,
            score: str = "softmax", select_bias=None, scale: float = 1.0):
    """The held experts' share of a top-k expert layer.

    Args:
      x: [N, D] this chip's tokens.
      router_w: [D, E] router over ALL experts (replicated), float32.
      w_up: [held, D, F], w_down: [held, F, D]: the experts held here;
        ``w_gate`` [held, D, F] makes them gated, ``silu(x Wg) * (x Wu)``;
        without it they are ``gelu(x Wu)``.
      top_k: experts per token; ``renormalize``: weights are the kept
        probabilities over their sum, else the probabilities themselves.
      first_expert: the first expert held here (on an ``axis_name`` of size
        n: by rank 0 of it; rank r holds the next ``held`` ones).
      axis_name: the mesh axis the experts are spread over, or None.
      score: ``"softmax"`` (probabilities over the E experts) or
        ``"sigmoid"`` (of each expert's logit by itself).
      select_bias: [E] or None: added to the scores to pick the ``top_k``
        and for nothing else (the weights are the unbiased scores of the
        kept; a constant to the backward: its update rule, from the load
        of the whole group, is not the loss's gradient).
      scale: a factor on the kept weights, after any renormalisation.

    Returns ``(y [N, D], stats)``: ``stats["aux"]`` is the load-balancing
    loss (Shazeer et al.: E x sum over experts of the share of assignments
    x the mean router probability; under sigmoid scores, the scores over
    their sum), ``stats["held_load"]`` [held] the
    assignments that fell to each held expert, ``stats["absent"]`` the
    assignments of these tokens that fell to experts not held here,
    ``stats["ids"]`` [N, top_k] the experts each token kept,
    ``stats["chunks"]`` (int32) the chunks of the held experts' sorted
    assignments that held a row and were worked (``_held_experts``: the
    load over a chunk's rows, rounded up; 1 under a balanced load).
    """
    N, _ = x.shape
    E, held = router_w.shape[1], w_up.shape[0]
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if score == "softmax":
            scores = probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            raise ValueError(f"moe_ffn: score {score!r} is not 'softmax' or "
                             f"'sigmoid'")
        if select_bias is None:
            top, ids = lax.top_k(scores, top_k)
        else:
            ids = lax.top_k(scores + lax.stop_gradient(
                select_bias.astype(jnp.float32)), top_k)[1]
            # The kept scores by a dense compare-and-sum over the experts:
            # XLA's TPU backend works a gather of N·k values one at a time.
            top = jnp.sum(jnp.where(
                ids[..., None] == jnp.arange(E, dtype=ids.dtype),
                scores[:, None, :], 0.0), axis=-1)
        gates = top / jnp.sum(top, -1, keepdims=True) if renormalize else top
        if scale != 1.0:
            gates = gates * scale
        # Assignments per expert by a dense compare-and-sum: XLA's TPU
        # scatter-add works its N·k values one at a time.
        counts = jnp.sum(ids[..., None] == jnp.arange(E, dtype=ids.dtype),
                         axis=(0, 1), dtype=jnp.int32)
        share = counts.astype(jnp.float32) / (N * top_k)
        aux = jnp.sum(share * jnp.mean(probs, axis=0)) * E
    ranks = 1 if axis_name is None else lax.axis_size(axis_name)
    spread = ranks > 1
    if held * ranks < E:
        # A token's weights are normalised over ALL its experts, so their
        # gradient needs every kept expert's output, and the absent ones'
        # are elsewhere. The part that can be computed here only ever says
        # "the experts held here help, the others do not": it would teach
        # the router to send everything here. It is left out, as the
        # absent experts' outputs are: the weights are constants to the
        # backward pass and the router gets no gradient from this share.
        gates = lax.stop_gradient(gates)
    xs, ids_s, gates_s = x, ids, gates
    if spread:
        first_expert = first_expert + lax.axis_index(axis_name) * held
        xs, ids_s, gates_s = (lax.all_gather(a, axis_name, tiled=True)
                              for a in (x, ids, gates))
    with jax.named_scope("moe.experts"):
        y, load, chunks = _held_experts(
            xs, ids_s, gates_s.astype(jnp.float32), w_gate, w_up, w_down,
            first_expert, E)
    if spread:
        y = lax.psum_scatter(y, axis_name, tiled=True)
    local = ids - first_expert
    absent = N * top_k - jnp.sum((local >= 0) & (local < held))
    return y, {"aux": aux, "held_load": load, "absent": absent, "ids": ids,
               "chunks": chunks}


_m_load = _registry().gauge(
    "hvd_moe_load_max_over_mean",
    "assignments to the busiest held expert over the mean of the held "
    "experts, by layer, as last recorded", labels=("layer",))
_m_held = _registry().gauge(
    "hvd_moe_held_assignments",
    "router assignments that fell to the experts held on this chip, by "
    "layer, as last recorded", labels=("layer",))
_m_absent = _registry().gauge(
    "hvd_moe_absent_assignments",
    "router assignments that fell to experts not held on this chip, by "
    "layer, as last recorded", labels=("layer",))


_m_chunks = _registry().gauge(
    "hvd_moe_chunks_entered",
    "chunks of sorted assignments that held a row for the experts held on "
    "this chip (1 under a balanced load; each one more is a chunk's worth "
    "of rows beyond 1.5 x balanced, which the step paid for), by layer, as "
    "last recorded", labels=("layer",))


def record_routing(layer: int, held_load, absent, chunks=None) -> None:
    """Stamp one layer's routing load (host values, off the dispatch path:
    from a step's small outputs after it completed); ``chunks``, where the
    caller has it, is ``moe_ffn``'s ``stats["chunks"]``."""
    load = [float(v) for v in held_load]
    mean = sum(load) / len(load)
    _m_load.labels(layer=str(layer)).set(max(load) / mean if mean else 0.0)
    _m_held.labels(layer=str(layer)).set(sum(load))
    _m_absent.labels(layer=str(layer)).set(float(absent))
    if chunks is not None:
        _m_chunks.labels(layer=str(layer)).set(float(chunks))
