"""Pallas flash-decoding paged-attention kernel (the serving decode hot op).

Parity target: the reference's fused paged/block-attention inference kernels
(Paddle Inference's ``block_multihead_attention`` / Phi fusion ops — the
layer PAPER.md credits for production decode speed) and the vLLM/
flash-decoding idiom they implement. The serving engine's XLA fallback path
(``models.generation.paged_decode_step`` gather + ``llama._masked_sdpa``)
materializes a dense ``[slots, W * block_size, Hk, D]`` gather of every
sequence's blocks and then masks most of it away — at long contexts decode
is bandwidth-bound on KV bytes the mask immediately discards.

TPU redesign, not a translation:

* **Block tables consumed IN-KERNEL.** The ``[M, W]`` block table and
  ``[M]`` sequence lengths ride in as scalar-prefetch operands
  (``pltpu.PrefetchScalarGridSpec``), so each grid step's K/V BlockSpec
  index map reads ``table[m, w]`` and DMAs exactly that physical block from
  the pool — the ``[slots, W*bs, ...]`` gather is never materialized in HBM.
* **Split-K across KV blocks, online-softmax merge.** The grid is
  ``(M, W)`` with the KV-block dimension innermost: each slot streams its
  blocks through VMEM accumulators (running max ``m``, normalizer ``l``,
  weighted-value ``acc``, one row set per kv head) and merges partials
  with the flash-decoding rescale ``alpha = exp(m_prev - m_cur)`` — the
  sequential spelling of split-K whose parallelism lives in the ``M`` grid
  cells (the same accumulator scheme as ``flash_attention.py``'s fwd
  kernel).
* **One block DMA serves every kv head.** The pool keeps the engine's
  ``[N, bs, Hk, D]`` layout and a K/V tile is the whole ``(1, bs, Hk, D)``
  block: its last two dims are the array's own, which is the only form
  of a one-block tile the TPU lowering accepts (a per-head ``(1, bs, 1,
  D)`` tile is refused — second-to-last block dim 1 against ``Hk``). The
  kv heads are a static loop INSIDE the grid cell, each head reading its
  ``[bs, D]`` rows out of the resident block, so the grid has ``Hk``
  times fewer steps and each step moves ``Hk`` times the bytes.
* **GQA grouped IN-KERNEL.** Queries arrive as ``[M, Hk, G, D]`` (the
  ``G = H // Hk`` query heads sharing one kv head form one tile), so each
  K/V block is read ONCE and each head's rows are scored against all its
  query heads — the gather path pays the ``jnp.repeat`` expansion instead.
* **int8 KV dequant fused into the loads.** Quantized pools
  (``kv_quant="int8"``: int8 blocks + per-token-per-head fp32 scales stored
  alongside, see ``models.generation.init_paged_pool``) dequantize in VMEM
  right after the block DMA — HBM only ever streams the int8 bytes, which
  is the capacity AND bandwidth win at once. A dense dequantized pool never
  exists anywhere.
* **Poison containment.** V rows at positions no query may attend
  (``j > seq_len``: the null block, stale tails of reused blocks) are
  zeroed before the PV matmul — the same containment contract as
  ``llama._masked_sdpa`` (0-weight * NaN would otherwise wipe the row), and
  bit-invisible for finite KV since those weights are exact 0.0.

The kernel compiles natively on TPU and runs in Pallas interpret mode
elsewhere (:mod:`paddle_tpu.kernels.dispatch`), so tier-1 exercises this
exact kernel body on the CPU; ``tests/test_chip_smoke.py`` additionally
pushes it through the TPU lowering at the serving preset's shapes.
Scale layout note: scales are stored ``[N, bs, Hk]`` to match the scatter
writes and ride in as ``(1, bs, Hk)`` tiles — ``Hk`` of 128 lanes used,
one lane-broadcast per head; revisit the layout if the scale DMA ever
shows up in profiles (the K/V streams dominate by ``D/4``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret as _interpret

__all__ = ["paged_attention"]

_NEG_INF = -1e30
# both in-kernel matmuls contract in fp32: the MXU's default single bf16
# pass would round the softmax weights (and an int8 pool's dequantized
# K/V) to 8 mantissa bits, which the CPU interpret-mode parity suites
# never see — the chip must compute the function the tests pin
_F32 = jax.lax.Precision.HIGHEST


def _kernel(*refs, bs, num_blocks_per_seq, scale, quant, Hk, G, Q):
    """One grid cell = (slot m, KV block w); the kv heads are a static
    loop inside it, so the ``[bs, Hk, D]`` block is DMA'd ONCE and every
    head reads its ``[bs, D]`` rows out of VMEM. ``Q = 1`` is the
    single-token decode step; ``Q > 1`` is the multi-query entry point —
    each head's query tile is ``[Q * G, D]`` (Q positions x G grouped
    query heads per kv head) and a third scalar-prefetch operand
    ``dl_ref`` carries each slot's draft length: query offset ``i``
    attends ``j <= sl + min(i, dl)`` (its committed KV plus the in-pass
    draft prefix; garbage rows past ``dl`` cap at ``dl`` so no row's
    window ever reaches an unwritten position)."""
    if Q > 1:
        tbl_ref, sl_ref, dl_ref = refs[:3]
        refs = refs[3:]
    else:
        tbl_ref, sl_ref = refs[:2]
        refs = refs[2:]
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = \
            refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    m = pl.program_id(0)
    w = pl.program_id(1)
    QG = Q * G

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    sl = sl_ref[m]
    dl = dl_ref[m] if Q > 1 else 0
    base = w * bs

    # skip blocks entirely past the attendable window (their table entries
    # point at the null block; compute is gated, accumulators pass through)
    @pl.when(base <= sl + dl)
    def _run():
        # every index vector stays rank 2 (Mosaic has no rank-1 layout):
        # jcol/jrow are the block's KV positions down sublanes / along lanes
        jcol = base + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        jrow = base + jax.lax.broadcasted_iota(jnp.int32, (QG, bs), 1)
        if Q > 1:                      # per-query-row causal draft window
            qi = jax.lax.broadcasted_iota(jnp.int32, (QG, bs), 0)
            if G > 1:
                qi = qi // G
            valid = jrow <= sl + jnp.minimum(qi, dl)     # [Q*G, bs]
        else:
            valid = jrow <= sl                           # [G, bs]
        # containment: V at never-attendable positions must be ZEROED, not
        # merely zero-weighted — a poisoned request can park NaN there
        # (see llama._masked_sdpa); exact 0.0 weights make this bit-invisible
        # for finite KV. The widest window any query row reaches is
        # j <= sl + dl (every position there was written this dispatch or
        # earlier), so the union can never touch a stale block tail.
        keep = jcol <= sl + dl                           # [bs, 1]
        for h in range(Hk):
            q = q_ref[0, h].astype(jnp.float32)          # [Q*G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)    # [bs, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quant:                  # dequant fused into the block load
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]
            v = jnp.where(keep, v, 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=_F32,
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, _NEG_INF)
            m_prev = m_ref[h]                            # [Q*G, 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=_F32,
                preferred_element_type=jnp.float32)
            m_ref[h] = m_cur

    @pl.when(w == num_blocks_per_seq - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _vmem_bytes(Hk, QG, D, bs, q_dtype, out_dtype, pool_dtype) -> int:
    """Scoped-VMEM request for one grid cell, from the shapes: the query
    and output tiles and the K/V blocks are double-buffered by the
    pipeline, the three accumulators are resident (``m``/``l`` pad their
    one column to a 128-lane tile), and the head loop's fp32 temporaries
    (query tile, scores, weights) each pad to 128 lanes. A mixed step's
    prefill chunk under GQA (``Q * G`` in the thousands) needs more than
    the compiler's 16 MiB default; a decode step far less."""
    lanes = 128
    isz = lambda dt: jnp.dtype(dt).itemsize
    rows = Hk * QG
    tiles = 2 * rows * D * (isz(q_dtype) + isz(out_dtype))
    kv = 2 * 2 * bs * max(Hk, 32) * (D * isz(pool_dtype) + 4)
    scratch = rows * (D + 2 * lanes) * 4
    temps = QG * (2 * D + 4 * lanes) * 4 + 4 * bs * max(D, lanes) * 4
    return max(16 << 20, int(1.25 * (tiles + kv + scratch + temps)))


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                    draft_lens=None, k_scale=None, v_scale=None,
                    scale: Optional[float] = None, out_dtype=None):
    """Decode attention for ``M`` serving slots straight off the block pool.

    ``q [M, H, D]`` — one query token per slot (the decode entry point) —
    or ``q [M, Q, H, D]`` with ``draft_lens [M]`` — ``Q`` query tokens
    per slot, the MULTI-QUERY entry point (speculative verify, and the
    mixed step's prefill chunks): query offset ``i`` of slot ``m`` sits at
    KV position ``seq_lens[m] + i`` and attends ``j <= seq_lens[m] +
    min(i, draft_lens[m])`` (committed KV plus the in-pass draft prefix;
    rows past the slot's real draft cap at ``draft_lens`` so no window
    reaches an unwritten position). ``k_pool``/``v_pool``
    ``[N, bs, Hk, D]`` — ONE layer's physical block pool (fp, or int8 with
    ``k_scale``/``v_scale [N, bs, Hk]`` fp32 per-token-per-head scales);
    ``block_tables [M, W]`` int32 — slot ``m``'s KV position ``j`` lives in
    physical block ``block_tables[m, j // bs]`` at offset ``j % bs``;
    ``seq_lens [M]`` int32 — slot ``m`` attends positions ``j <=
    seq_lens[m]`` (its new token's KV was just scattered at ``seq_lens[m]``).
    Unassigned table entries must point at the null block 0. Returns
    ``[M, H, D]`` (or ``[M, Q, H, D]``) in ``out_dtype`` (default: the
    pool dtype for fp pools, fp32 for int8 pools — matching the gather
    path's ``_masked_sdpa`` output dtype).
    """
    multi = q.ndim == 4
    if multi:
        M, Q, H, D = q.shape
        if draft_lens is None:
            raise ValueError("paged_attention: multi-query (verify) calls "
                             "need draft_lens")
    else:
        M, H, D = q.shape
        Q = 1
        if draft_lens is not None:
            raise ValueError("paged_attention: draft_lens given with a "
                             "single-token q [M, H, D]; the verify entry "
                             "point takes q [M, Q, H, D]")
    N, bs, Hk, _ = k_pool.shape
    W = block_tables.shape[1]
    if H % Hk:
        raise ValueError(f"paged_attention: {H} query heads not divisible "
                         f"by {Hk} kv heads")
    G = H // Hk
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("paged_attention: k_scale and v_scale must be "
                         "given together")
    if out_dtype is None:
        out_dtype = jnp.float32 if quant else k_pool.dtype
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    # GQA grouping: query head h = kh * G + g shares kv head kh — exactly
    # the jnp.repeat(k, G, axis=heads) correspondence the fallback expands.
    # Multi-query tiles stack the Q draft positions above the group: row
    # q * G + g of kv head kh is query offset q's head kh * G + g.
    if multi:
        qg = q.reshape(M, Q, Hk, G, D).transpose(0, 2, 1, 3, 4) \
              .reshape(M, Hk, Q * G, D)
    else:
        qg = q.reshape(M, Hk, G, D)
    QG = Q * G
    tbl = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    # scalar-prefetch operands: (tbl, sl) for decode, + dl for multi-query
    # — every index map takes them positionally after the grid indices
    scalars = (tbl, sl, jnp.asarray(draft_lens, jnp.int32)) if multi \
        else (tbl, sl)

    def qmap(m, w, tbl, *_):
        return (m, 0, 0, 0)

    def kvmap(m, w, tbl, *_):
        return (tbl[m, w], 0, 0, 0)

    def smap(m, w, tbl, *_):
        return (tbl[m, w], 0, 0)

    # every block's last two dims equal the array's own ((Hk, D) for the
    # pool, (bs, Hk) for the scale planes, (QG, D) for the query tile):
    # the TPU lowering accepts a full-extent tile at any size, which a
    # one-head (1, D) slice of the pool is not
    in_specs = [
        pl.BlockSpec((1, Hk, QG, D), qmap),
        pl.BlockSpec((1, bs, Hk, D), kvmap),
        pl.BlockSpec((1, bs, Hk, D), kvmap),
    ]
    ops = [qg, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, Hk), smap),
                     pl.BlockSpec((1, bs, Hk), smap)]
        ops += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(M, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hk, QG, D), qmap),
        scratch_shapes=[
            pltpu.VMEM((Hk, QG, D), jnp.float32),
            pltpu.VMEM((Hk, QG, 1), jnp.float32),
            pltpu.VMEM((Hk, QG, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, num_blocks_per_seq=W, scale=scale,
                          quant=quant, Hk=Hk, G=G, Q=Q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, Hk, QG, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(Hk, QG, D, bs, q.dtype, out_dtype,
                                         k_pool.dtype)),
        interpret=_interpret(),
        # the trace and the compiled text name the custom call after this:
        # the decode form and the multi-query (mixed / verify) form are
        # two kernels to a profile, so they carry two names
        name="paged_attention_mq" if multi else "paged_attention_q1",
    )(*scalars, *ops)
    if multi:
        return out.reshape(M, Hk, Q, G, D).transpose(0, 2, 1, 3, 4) \
                  .reshape(M, Q, H, D)
    return out.reshape(M, H, D)
