"""Pallas fused RMSNorm (forward + backward).

Parity target: the reference's ``fused_rms_norm`` GPU kernel
(``paddle/phi/kernels/fusion/gpu/`` fused_rms_norm / rms_norm_kernel). TPU redesign:
one VMEM-resident Pallas kernel computing x * rsqrt(mean(x^2)+eps) * w row-blockwise
(saves the rstd for backward); backward is a second kernel producing dx and a
per-row-block partial dw reduced on the host side of the kernel boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import interpret as _interpret

__all__ = ["rms_norm"]


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dwp_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat, -1))
    m = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wg - xhat * m)).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        dwp_ref[:] = jnp.zeros_like(dwp_ref)

    # accumulate the weight grad across row blocks (same (8, d) block revisited
    # every grid step; every sublane row carries the full sum — row 0 is read back)
    part = jnp.sum(g * xhat, axis=0, keepdims=True)
    dwp_ref[:] += jnp.broadcast_to(part, dwp_ref.shape)


def _block_rows(n_rows: int) -> int:
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n_rows % b == 0:
            return b
    return 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm over the last axis: x * rsqrt(mean(x^2)+eps) * weight."""
    out, _ = _fwd(x, weight, eps)
    return out


def _fwd(x, weight, eps):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    br = _block_rows(n)
    out, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="rms_norm_fwd",
    )(x2, weight.reshape(1, d))
    return out.reshape(shape), rstd


def _rms_fwd_rule(x, weight, eps):
    out, rstd = _fwd(x, weight, eps)
    return out, (x, weight, rstd)


def _rms_bwd_rule(eps, res, g):
    x, weight, rstd = res
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    g2 = g.reshape(-1, d)
    n = x2.shape[0]
    br = _block_rows(n)
    dx, dwp = pl.pallas_call(
        _bwd_kernel,
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((8, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((8, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="rms_norm_bwd",
    )(x2, weight.reshape(1, d), rstd, g2)
    dw = dwp[0].astype(weight.dtype)
    return dx.reshape(shape), dw


rms_norm.defvjp(_rms_fwd_rule, _rms_bwd_rule)
