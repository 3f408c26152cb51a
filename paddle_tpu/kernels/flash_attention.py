"""Pallas TPU flash attention (forward + backward).

Parity target: the reference's fused attention stack —
``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` (FlashAttention-2 wrapper around
``third_party/flashattn``) and the cutlass memory-efficient fallback. TPU redesign:
a Mosaic/Pallas kernel with the online-softmax streaming algorithm, kv blocks on the
innermost grid dimension (accumulators in VMEM scratch), bf16-friendly, causal and
grouped-query (GQA) support, O(S) memory. The backward pass recomputes attention
blockwise from the saved logsumexp (no S×S materialization), matching the
flash-attention-2 recipe.

Layout: paddle's [batch, seq, heads, head_dim]; internally [B, H, S, D].
Interpret mode (CPU testing) is selected automatically off the backend.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret as _interpret

__all__ = ["flash_attention", "flash_attention_with_lse"]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _seg_overlap(sq_ref, sk_ref):
    """Whether this [block_q, block_k] tile can contain ANY same-segment
    pair: the segment-id RANGES of the two tiles must intersect. Sound for
    arbitrary segment ids (range test is conservative); for the packed
    layout (ids non-decreasing along the sequence — the varlen contract)
    it is exact, and skipping the disjoint tiles makes the kernel's work
    scale with the number of same-segment blocks rather than S^2 — the
    splash/sparse-causal structure of the reference's varlen kernels."""
    sq = sq_ref[0, :, 0]
    sk = sk_ref[0, :, 0]
    return (jnp.min(sq) <= jnp.max(sk)) & (jnp.min(sk) <= jnp.max(sq))


def _gate(pred_static, sq_ref, sk_ref, use_seg, run):
    """Combine the causal block gate (None = always run) with the segment
    block-skip predicate and execute ``run`` under it."""
    pred = pred_static
    if use_seg:
        ov = _seg_overlap(sq_ref, sk_ref)
        pred = ov if pred is None else jnp.logical_and(pred, ov)
    if pred is None:
        run()
    else:
        pl.when(pred)(run)


def _fwd_kernel(*refs, scale, causal, causal_offset, block_q,
                block_k, num_kv_blocks, use_seg):
    if use_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    kb = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kb * block_k

    def run():
        q = q_ref[0, 0].astype(jnp.float32)          # [Bq, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            # bottom-right alignment (flash-attention-2 / _sdpa_ref tril(k=Sk-Sq)
            # convention): query i attends keys j with j <= i + (Sk - Sq)
            rows = q_start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if use_seg:
            # varlen/packed sequences: attend only within a segment
            seg_mask = sq_ref[0, :, 0][:, None] == sk_ref[0, :, 0][None, :]
            s = jnp.where(seg_mask, s, _NEG_INF)
        m_prev = m_ref[:, 0]                          # [Bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        if use_seg:
            # a row with NO visible keys so far has m_cur == _NEG_INF and
            # s - m_cur == 0 -> exp would emit spurious 1s; zero them
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    # causal: skip blocks strictly above the (bottom-right-aligned)
    # diagonal; varlen: additionally skip tiles with no same-segment pair
    _gate(k_start <= q_start + block_q - 1 + causal_offset if causal
          else None,
          sq_ref if use_seg else None, sk_ref if use_seg else None,
          use_seg, run)

    @pl.when(kb == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = m_ref[:, 0] + jnp.log(safe_l)


def _seg_operands(seg_q, seg_k, block_q, block_k, q_grid_dim: int = 2):
    """Segment ids as [B, S, 1] with per-batch (1, block, 1) blocks.
    ``q_grid_dim`` names which grid dim walks q blocks (2 for fwd/dq whose
    grid is (B,H,nq,nk); 3 for dkv whose grid is (B,H,nk,nq)).
    Returns ([], []) on the dense path: no operands, no wasted bandwidth."""
    if seg_q is None:
        return [], []
    # [B, S, 1] with (1, block, 1) blocks — same layout family as the
    # lse/delta operands (minor dim 1 equals the array dim, second-to-minor
    # is the 8-divisible block), per-batch DMA traffic
    sq = jnp.asarray(seg_q, jnp.int32)[..., None]
    sk = jnp.asarray(seg_k, jnp.int32)[..., None]
    if q_grid_dim == 2:
        qmap = lambda b, h, i2, i3: (b, i2, 0)  # noqa: E731
        kmap = lambda b, h, i2, i3: (b, i3, 0)  # noqa: E731
    else:
        qmap = lambda b, h, i2, i3: (b, i3, 0)  # noqa: E731
        kmap = lambda b, h, i2, i3: (b, i2, 0)  # noqa: E731
    specs = [pl.BlockSpec((1, block_q, 1), qmap),
             pl.BlockSpec((1, block_k, 1), kmap)]
    return [sq, sk], specs


def _fwd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k):
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    group = H // Hk
    nq = Sq // block_q
    nk = Sk // block_k
    seg_ops, seg_specs = _seg_operands(seg_q, seg_k, block_q, block_k)

    grid = (B, H, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          causal_offset=Sk - Sq, block_q=block_q,
                          block_k=block_k, num_kv_blocks=nk,
                          use_seg=bool(seg_ops)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kb: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, kb, g=group: (b, h // g, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, kb, g=group: (b, h // g, kb, 0)),
            *seg_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kb: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, kb: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, D), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(q, k, v, *seg_ops)
    return out, lse


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, causal_offset,
                   block_q, block_k, num_kv_blocks, use_seg):
    if use_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
    kb = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kb * block_k

    def run():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if use_seg:
            seg_mask = sq_ref[0, :, 0][:, None] == sk_ref[0, :, 0][None, :]
            s = jnp.where(seg_mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if use_seg:  # fully-masked rows have lse == _NEG_INF: avoid exp(0)=1
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _gate(k_start <= q_start + block_q - 1 + causal_offset if causal
          else None,
          sq_ref if use_seg else None, sk_ref if use_seg else None,
          use_seg, run)

    @pl.when(kb == num_kv_blocks - 1)
    def _fin():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, causal_offset, block_q, block_k,
                    num_q_blocks, use_seg):
    if use_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    qb = pl.program_id(3)
    ki = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qb * block_q
    k_start = ki * block_k

    def run():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if use_seg:
            seg_mask = sq_ref[0, :, 0][:, None] == sk_ref[0, :, 0][None, :]
            s = jnp.where(seg_mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                                  # [Bq,Bk]
        if use_seg:
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _gate(k_start <= q_start + block_q - 1 + causal_offset if causal
          else None,
          sq_ref if use_seg else None, sk_ref if use_seg else None,
          use_seg, run)

    @pl.when(qb == num_q_blocks - 1)
    def _fin():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, seg_q, seg_k, out, lse = res
    do, _ = g
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    group = H // Hk
    nq = Sq // block_q
    nk = Sk // block_k
    seg_ops, seg_specs = _seg_operands(seg_q, seg_k, block_q, block_k,
                                       q_grid_dim=2)
    use_seg = bool(seg_ops)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [B,H,Sq,1]
    lse = lse[..., None] if lse.ndim == 3 else lse

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          causal_offset=Sk - Sq, block_q=block_q,
                          block_k=block_k, num_kv_blocks=nk, use_seg=use_seg),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kb: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, kb, g_=group: (b, h // g_, kb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, kb, g_=group: (b, h // g_, kb, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kb: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, kb: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, kb: (b, h, qi, 0)),
            *seg_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, kb: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[_vmem((block_q, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta, *seg_ops)

    # dk/dv accumulate over q blocks, one pass per kv head group member then sum
    seg_ops2, seg_specs2 = _seg_operands(seg_q, seg_k, block_q, block_k,
                                         q_grid_dim=3)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          causal_offset=Sk - Sq, block_q=block_q,
                          block_k=block_k, num_q_blocks=nq, use_seg=use_seg),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qb, g_=group: (b, h // g_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qb, g_=group: (b, h // g_, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ki, qb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ki, qb: (b, h, qb, 0)),
            *seg_specs2,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qb: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qb: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
        ],
        scratch_shapes=[_vmem((block_k, D), jnp.float32),
                        _vmem((block_k, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta, *seg_ops2)

    if group > 1:  # GQA: fold query-head groups back onto kv heads
        dk = dk.reshape(B, Hk, group, Sk, D).sum(axis=2)
        dv = dv.reshape(B, Hk, group, Sk, D).sum(axis=2)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None)


# ---------------------------------------------------------------------------
# public entry (custom_vjp, paddle [B, S, H, D] layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_bhsd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k)
    return out, _


def _flash_fwd_rule(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k)
    # checkpoint-policy names: save_only_these_names("flash_out","flash_lse")
    # keeps the kernel's residuals across remat so backward never re-runs
    # the fwd kernel (the dominant recompute term in the full-remat LLaMA
    # step — see BASELINE.md roofline); memory cost is o (bf16) + lse (f32
    # [B,H,S]) per layer, far below the "dots" policies' [B,S,I] saves
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q, k, v, seg_q, seg_k, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, res, g):
    return _bwd(scale, causal, block_q, block_k, res, g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _default_blocks(Sq: int, Sk: int):
    """TPU-tuned defaults (v5e fwd+bwd sweep at S=2048, D=64 and D=128:
    (1024,1024) is ~25% faster than (1024,512) — 11.5/11.9 ms vs 15.4/16.1 —
    and tiny 128x128 blocks are 1.7x SLOWER than the jnp reference).
    Interpret mode (CPU tests) keeps small blocks for speed."""
    if _interpret():
        return min(128, Sq), min(128, Sk)
    return min(1024, Sq), min(1024, Sk)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             segment_ids=None, kv_segment_ids=None):
    """[B, S, H, D] flash attention returning (out, lse[B, H, S]).

    ``segment_ids`` [B, Sq] (int) enables varlen/packed-sequence masking:
    tokens attend only within their segment (the TPU-native form of the
    reference's ``flash_attn_varlen`` / cu_seqlens API — pack the sequences
    and label each with its index). ``kv_segment_ids`` defaults to
    ``segment_ids`` (self-attention).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    dq, dk = _default_blocks(Sq, Sk)
    block_q = min(block_q or dq, Sq)
    block_k = min(block_k or dk, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(f"flash_attention: seq lens ({Sq},{Sk}) must divide "
                         f"block sizes ({block_q},{block_k})")
    if causal and Sq > Sk:
        # bottom-right alignment leaves rows i < Sq-Sk attending nothing; the
        # softmax there is undefined (the jnp oracle yields NaN) — reject rather
        # than return silently wrong finite values
        raise ValueError(f"flash_attention: causal with Sq ({Sq}) > Sk ({Sk}) "
                         f"has fully-masked query rows; mask them explicitly "
                         f"or pad keys")
    if segment_ids is not None and kv_segment_ids is None:
        if Sq != Sk:
            raise ValueError("flash_attention: kv_segment_ids required when "
                             "Sq != Sk")
        kv_segment_ids = segment_ids
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out, lse = _flash_bhsd(qt, kt, vt, segment_ids, kv_segment_ids,
                           float(scale), bool(causal),
                           int(block_q), int(block_k))
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    segment_ids=None, kv_segment_ids=None):
    """[B, S, H, D] flash attention (the paddle flash_attn kernel equivalent;
    ``segment_ids`` = varlen/packed mode)."""
    out, _ = flash_attention_with_lse(q, k, v, causal, scale, block_q, block_k,
                                      segment_ids, kv_segment_ids)
    return out
