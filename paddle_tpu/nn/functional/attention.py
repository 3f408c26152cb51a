"""Attention functionals.

Parity target: ``paddle.nn.functional.scaled_dot_product_attention`` (reference:
``python/paddle/nn/functional/flash_attention.py``, backed by
``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` wrapping third_party/flashattn).
TPU redesign: on TPU the Pallas flash-attention kernel (kernels/flash_attention.py) runs
for unmasked, dropout-free calls whose head_dim and query length are multiples of 128;
the jnp path below is the reference implementation, and what every other call runs.
Layout is paddle's [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops._helpers import ensure_tensor, forward_op


def _sdpa_ref(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None,
              dropout_key=None):
    """Pure-jax reference attention on [B, S, H, D]."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B,H,S,D] layout for the matmuls
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cmask, logits, -jnp.inf)
    if mask is not None:
        if jnp.issubdtype(mask.dtype, jnp.bool_):
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Flash attention entry (paddle layout [B, S, H, D]).

    Uses the Pallas TPU kernel on TPU when the shapes allow (see ``_pallas_ok``),
    else the jnp reference.
    """
    query, key, value = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    args = [query, key, value]
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))

    dk = None
    if dropout_p > 0.0 and training:
        from ...ops.random import _next_key
        dk = _next_key()

    use_pallas = _pallas_ok(query, attn_mask, dropout_p if training else 0.0)

    def impl(q, k, v, *m):
        if use_pallas:
            from ...kernels.flash_attention import flash_attention
            return flash_attention(q, k, v, causal=is_causal)
        return _sdpa_ref(q, k, v, m[0] if m else None,
                         dropout_p if training else 0.0, is_causal, dropout_key=dk)

    return forward_op("scaled_dot_product_attention", impl, args)


def _pallas_ok(q, mask, dropout_p) -> bool:
    if mask is not None or dropout_p > 0.0:
        return False
    from ...kernels.dispatch import on_tpu
    if not on_tpu():
        return False
    d = q.shape[-1]
    sq = q.shape[1]
    return d % 128 == 0 and sq % 128 == 0


def attention_probs(query, key, attn_mask=None, scale=None):
    """Materialized softmax attention weights [B, H, Sq, Sk] (need_weights path)."""
    query, key = ensure_tensor(query), ensure_tensor(key)
    args = [query, key]
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))

    def impl(q, k, *m):
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
        if m:
            mask = m[0]
            if jnp.issubdtype(mask.dtype, jnp.bool_):
                logits = jnp.where(mask, logits, -jnp.inf)
            else:
                logits = logits + mask
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)

    return forward_op("attention_probs", impl, args)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None, fixed_seed_offset=None,
                    rng_name="", training=True):
    """paddle.nn.functional.flash_attention.flash_attention parity: returns
    (out, softmax); softmax is None unless return_softmax (flash never materializes
    the probability matrix — same contract as the reference kernel)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal,
                                       training)
    return out, None


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    x = ensure_tensor(x)
    ml = int(maxlen) if maxlen is not None else int(x.numpy().max())
    from ...core.dtype import canonical_dtype
    dt = canonical_dtype(dtype)

    def impl(v):
        return (jnp.arange(ml) < v[..., None]).astype(dt)

    return forward_op("sequence_mask", impl, [x], differentiable=False)
