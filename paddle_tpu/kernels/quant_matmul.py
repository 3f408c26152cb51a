"""Weight-only int8 matmul Pallas kernel.

Parity target: the reference's weight-only quantization path
(``paddle.nn.quant.weight_only_linear`` / ``llm.int8`` kernels under
``paddle/phi/kernels/fusion/``). TPU rationale: LLM inference matmuls are
HBM-BANDWIDTH bound on the weight stream — storing W as int8 + a per-column
fp scale halves the bytes read per step vs bf16. The kernel streams int8
blocks into VMEM, dequantizes in-register, and feeds the MXU in bf16; the
XLA-composed equivalent (``x @ (w.astype(bf16) * scale)``) materializes the
dequantized [K, N] matrix through HBM when it can't fuse, paying the full
bf16 bandwidth.

API:
  * :func:`quantize_weights`  — symmetric per-column int8 quantization.
  * :func:`weight_only_matmul` — ``x [..., K] @ w_int8 [K, N] -> [..., N]``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret as _interpret

__all__ = ["quantize_weights", "weight_only_matmul"]


def quantize_weights(w) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization of ``w [K, N]``:
    returns ``(w_int8 [K, N], scale [N])`` with ``w ≈ w_int8 * scale``."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wb = w_ref[...].astype(jnp.bfloat16)          # int8 -> bf16 in VMEM
    acc_ref[...] += jnp.dot(x_ref[...], wb,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _out():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def weight_only_matmul(x, w_q, scale, *, block_m: Optional[int] = None,
                       block_n: int = 512, block_k: int = 512,
                       out_dtype=jnp.bfloat16):
    """``x [..., K] (bf16) @ dequant(w_q [K, N] int8, scale [N]) ->
    [..., N]``; the dequantization happens in VMEM, so HBM only ever sees
    the int8 weights (the whole point)."""
    x = jnp.asarray(x)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]
    bm = block_m or min(256, max(8, M))
    bn = min(block_n, N)
    bk = min(block_k, K)

    def xla_fallback():
        out = xm.astype(jnp.bfloat16) @ (
            w_q.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)[None, :])
        return out.astype(out_dtype).reshape(*lead, N)

    if M % bm or N % bn or K % bk:
        return xla_fallback()        # shape not blockable
    nk = K // bk
    grid = (M // bm, N // bn, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            # scale as [1, N]: 1-D operands clash with XLA's tiled layout
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret(),
        name="quant_matmul",
    )(xm.astype(jnp.bfloat16), w_q, scale.reshape(1, N))
    return out.reshape(*lead, N)
