"""RMSNorm — the normalization used by the Qwen3-family models.

TPU-native analog of the reference's layer_norm use inside DenseLLMLayer
(ref: python/triton_dist/models/dense.py:101-114; the reference calls
flashinfer/torch rmsnorm). On TPU this is a pure-XLA elementwise chain that
fuses into neighbouring matmuls; a hand kernel would only hurt.
"""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-6, zero_centred: bool = False):
    """y = x / rms(x) * weight, computed in f32, returned in x.dtype.

    Qwen3 also applies per-head "qk norm" with the same function over the
    head_dim axis (weight broadcast over heads). zero_centred: the gain
    is (1 + weight), Qwen3-Next's form (a zero weight is the identity).
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    gain = weight.astype(jnp.float32)
    if zero_centred:
        gain = 1.0 + gain
    return (y * gain).astype(x.dtype)
