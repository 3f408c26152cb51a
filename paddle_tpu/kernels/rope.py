"""Fused rotary position embedding.

Parity target: the reference's ``fused_rope`` kernel
(``paddle/phi/kernels/fusion/gpu/fused_rope_*``). TPU redesign: the rotate-half
formulation as a single VMEM-resident Pallas kernel over [rows, head_dim] blocks;
backward is the same rotation with the angle sign flipped (exact adjoint), via
custom_vjp so no trig recomputation graph is kept.

Layout: q/k as [B, S, H, D]; cos/sin as [S, D] (broadcast over batch and heads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import interpret as _interpret

__all__ = ["apply_rope", "rope_cos_sin"]


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)            # [S, D]
    cos = cos_ref[:].astype(jnp.float32)
    sin = sin_ref[:].astype(jnp.float32)
    d = x.shape[-1]
    x1 = x[:, : d // 2]
    x2 = x[:, d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[0] = (x * cos + rot * sin).astype(o_ref.dtype)


def _run(x, cos, sin):
    B, S, H, D = x.shape
    xf = jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)
    out = pl.pallas_call(
        _rope_kernel,
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((1, S, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((S, D), lambda i: (0, 0)),
            pl.BlockSpec((S, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, D), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), x.dtype),
        interpret=_interpret(),
        name="rope",
    )(xf, cos, sin)
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)


@jax.custom_vjp
def apply_rope(x, cos, sin):
    """Rotate-half RoPE: x*cos + rotate_half(x)*sin on [B, S, H, D]."""
    return _run(x, cos, sin)


def _rope_fwd(x, cos, sin):
    return _run(x, cos, sin), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    # adjoint of the rotation = rotation by -theta
    return _run(g, cos, -sin), None, None


apply_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_cos_sin(seq_len: int, head_dim: int, base: float = 10000.0,
                 dtype=jnp.float32, position_ids=None):
    """cos/sin tables [S, D] for the rotate-half convention."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = (jnp.arange(seq_len, dtype=jnp.float32) if position_ids is None
           else jnp.asarray(position_ids, jnp.float32))
    freqs = jnp.outer(pos, inv)                  # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)
