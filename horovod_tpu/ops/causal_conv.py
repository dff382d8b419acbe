"""The linear-attention mixers' depthwise causal convolution over time with
its SiLU, as one op: ``y = silu(conv(x, w))``, x [B, T, C], w [W, C],

    conv(x, w)_t = sum_j w[j] x_{t - (W - 1) + j}

with zeros before the start (no bias; no state carried in from another
sequence). Gated DeltaNet and Kimi Delta Attention run it over q, k and v
between their input projection and the rule (``parallel/transformer.py``
``_gdn_mixer``, ``_kda_mixer``).

Two forms. ``"xla"`` is plain ``jax.numpy`` differentiated by jax: W
shifted float32 copies summed, rounded to x's dtype, then ``jax.nn.silu``.
``"pallas"`` is the kernels ``conv_silu_fwd`` / ``conv_silu_bwd``
(``ops/pallas_causal_conv.py``) under a ``jax.custom_vjp`` that keeps x and
w alone: each way is one pass over the tensor, float32 from the load to one
rounding at the store, and the backward makes ``pre`` again from x. Where
the kernels cannot tile the shape (:func:`pallas_causal_conv.tile`) the
``"pallas"`` backend runs the XLA form; the gauge
``hvd_conv_kernel{layer}`` says which was traced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.registry import registry as _registry
from .gated_delta import resolve_backend


def _causal_conv(x, w):
    """Depthwise convolution over time, causal: x [B, T, C], w [W, C];
    ``y_t = sum_j w[j] x_{t - (W - 1) + j}`` with zeros before the start
    (no bias; no state carried in from another sequence)."""
    W, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(W))
    return y.astype(x.dtype)


@jax.custom_vjp
def _conv_silu(x, w):
    from . import pallas_causal_conv as pcc
    return pcc.conv_silu_fwd(x, w)


def _keep_inputs(x, w):
    return _conv_silu(x, w), (x, w)


def _vjp(res, dy):
    from . import pallas_causal_conv as pcc
    x, w = res
    dx, dw = pcc.conv_silu_bwd(x, w, dy)
    wider = x.shape[-1] - w.shape[-1]
    return jnp.pad(dx, ((0, 0), (0, 0), (0, wider))), dw.astype(w.dtype)


_conv_silu.defvjp(_keep_inputs, _vjp)


def causal_conv_silu(x, w, *, backend: str = "auto", layer=None):
    """``silu(conv(x[..., :C], w))`` [B, T, C] in x's dtype for x [B, T, Cx]
    and taps w [W, C], Cx >= C: the columns past C are another consumer's
    (a projection's output is handed over whole, not a slice of it: under
    ``jax.checkpoint`` a slice is a copy). ``backend``: ``"xla"``,
    ``"pallas"`` (on a CPU: the kernels in interpret mode; a shape they
    cannot tile takes the XLA form) or ``"auto"``. ``layer``: the calling
    layer's index, for the gauge ``hvd_conv_kernel{layer}``."""
    from . import pallas_causal_conv as pcc
    (W, C), T = w.shape, x.shape[1]
    kernels = (resolve_backend(backend) == "pallas"
               and pcc.tile(T, C, W, x.dtype) is not None)
    if layer is not None:
        _m_kernel.labels(layer=str(layer)).set(int(kernels))
    if kernels:
        return _conv_silu(x, w)
    if x.shape[-1] != C:
        x = x[..., :C]
    return jax.nn.silu(_causal_conv(x, w))


_m_kernel = _registry().gauge(
    "hvd_conv_kernel",
    "1 where the layer's causal convolution + SiLU was traced as the "
    "kernels conv_silu_fwd / conv_silu_bwd, 0 where the backend or its "
    "shapes sent it to jax.numpy", labels=("layer",))
