"""Pallas flash-decoding paged-attention kernel (the serving decode hot op).

Parity target: the reference's fused paged/block-attention inference kernels
(Paddle Inference's ``block_multihead_attention`` / Phi fusion ops — the
layer PAPER.md credits for production decode speed) and the vLLM/
flash-decoding idiom they implement. The serving engine's XLA fallback path
(``models.generation.paged_decode_step`` gather + ``llama._masked_sdpa``)
materializes a dense ``[slots, W * block_size, Hk, D]`` gather of every
sequence's blocks and then masks most of it away — at long contexts decode
is bandwidth-bound on KV bytes the mask immediately discards.

TPU redesign, not a translation. One rule: **the kernel's work follows
the real tokens** — pages a slot holds, query rows a slot carries.

* **Grid ``(M,)``, and inside a slot a loop over its LIVE cells.** A cell
  is ``P`` KV pages, ``P`` chosen from the shapes so that ``P * bs`` is
  about 256 KV positions (16 pages of 16; ``_tiling``. 128 until PR 34:
  what a (cell, head) pays whatever it holds — the accumulators read and
  rescaled, the copies waited for — is paid half as often). A slot
  attending ``j <= sl + dl`` runs ``ceil(pages / P)`` cells, ``pages = (sl
  + dl) // bs + 1``: no cell, no copy and no branch exists for the rest of
  the ``W``-wide table (a grid over the table's width paid 0.16 us for
  every page a slot did NOT hold, more than for the ones it did). ``W``
  need not divide by ``P``; the last cell's missing pages mask by position.
* **Block tables consumed IN-KERNEL, pages copied by the kernel.** The
  ``[M, W]`` block table and ``[M]`` lengths ride in as scalar-prefetch
  operands; the K and V pools stay in HBM (``memory_space=ANY``) and the
  kernel copies each live page ``tbl[m, c * P + i]`` — the engine's whole
  ``(Hk, bs, D)`` block, one copy serving every kv head — into a two-slot
  VMEM buffer, starting cell ``c + 1``'s copies before it computes cell
  ``c``. The ``[slots, W*bs, ...]`` gather is never materialized in HBM.
  The pools may be EVERY layer's, ``[L, N, Hk, bs, D]``, with the layer a
  further scalar-prefetch operand (``pool.at[layer, blk]`` is the copy's
  source): a caller whose layer scan carries the whole pool hands it over
  as it is, and no layer's slab is ever sliced out (by the pool's rank
  alone: one kernel, one more index).
* **One matmul pair a kv head a cell a query tile, and a cell's own work
  once a cell.** The kv heads are a static loop inside the cell; a head
  stacks its ``P * bs`` rows out of the resident pages (the pool is
  HEAD-MAJOR, a page ``(Hk, bs, D)``: a head's rows of a page are one
  whole ``(bs, D)`` tile) and does ONE score matmul, ONE value matmul and
  ONE online-softmax update (running max ``m``, normalizer ``l``, weighted
  values ``acc``, merged with ``alpha = exp(m_prev - m_cur)`` — the
  sequential spelling of split-K, as in ``flash_attention.py``'s forward
  kernel). A row of ONE query tile (a decode row, the ``Q = 1`` form)
  reads the rows as it goes. A chunk row of SEVERAL sub-tiles does
  whatever the cell and the head alone decide ONCE a cell: every head's K
  and V rows into a head-major VMEM scratch ``[Hk, P * bs, D]``, V
  contained (below) and only in a cell that holds a position to contain;
  its sub-tiles then read them contiguously, all heads of a sub-tile in
  one unrolled run (the compiler overlaps one head's matmuls with the next
  one's softmax: a branch or a loop INSIDE a head's work costs more than
  the stacking it saves, PERF.md §6). What the row alone decides is done
  once a slot: each tile row's query offset, capped at ``dl``, is divided
  out of its row index when the accumulators are cleared (``qpos``), and a
  cell's masks add ``sl`` to it.
* **GQA grouped IN-KERNEL.** Queries arrive as ``[M, Hk, Q * G, D]`` (row
  ``q * G + g`` of kv head ``kh`` is query offset ``q``'s head ``kh * G +
  g``), so each page is read ONCE and each head's rows are scored against
  all its query heads — the gather path pays the ``jnp.repeat`` instead.
* **A query tile sized by the slot's own length.** The multi-query entry
  point reads ``dl`` for its slot and runs only the rows ``q <= dl``: a
  ``dl == 0`` slot (a decoding slot inside a mixed step) runs the
  ``R0``-row tile — its ``G`` heads padded to the query dtype's sublane
  tile, exactly the decode step's work; a longer one runs ``ceil((dl + 1)
  * G / TQ)`` sub-tiles of ``TQ <= 128`` rows against the cell's stacked
  rows. The branch is on ``draft_lens`` alone. **Output rows past ``dl``
  are zeros** (until PR 25 they held a capped-window result nobody read):
  ``paged_mixed_step`` takes row ``dl``, the speculative verify masks by
  ``draft_lens``.
* **Passes that multiply by zero are not run.** bf16 queries against a
  bf16 or int8 pool contract as bf16 — one MXU pass, exact products, fp32
  accumulation: the sum ``HIGHEST`` computes over the cast operands — and
  the fp32 softmax weights meet such values in three bf16 terms
  (``_weighted_values``). fp32 operands keep ``HIGHEST``. By dtype only.
* **int8 KV: scales on the columns.** Quantized pools (``kv_quant=
  "int8"``: int8 blocks + per-token-per-head fp32 scales, see
  ``models.generation.init_paged_pool``) stream int8 pages — the capacity
  AND bandwidth win at once — and dequantize as ``(q · kᵀ) * ks`` and ``(p
  * vs) · v``: the scale of KV position ``j`` multiplies column ``j``. A
  dense dequantized pool never exists. The scale planes are ``[N, Hk,
  bs]`` and a page's ``(Hk, bs)`` tile is narrower than a kernel's own
  copy may slice from HBM, so the caller-side wrapper gathers them by
  table into ``[M, cells, Hk, P * bs]`` (1/32 of the pool's bytes; the
  one dense thing left — revisit with the scale layout, PERF.md §7).
* **Poison containment.** V rows at positions no query may attend
  (``j > sl + dl``: the null block, stale tails of reused blocks, pages
  of a cell that were never copied) are zeroed before the PV matmul —
  for an int8 pool the V scale is, the value being finite — the same
  containment contract as ``llama._masked_sdpa`` (0-weight * NaN would
  otherwise wipe the row), and bit-invisible for finite KV since those
  weights are exact 0.0.

The kernel compiles natively on TPU and runs in Pallas interpret mode
elsewhere (:mod:`paddle_tpu.kernels.dispatch`), so tier-1 exercises this
exact kernel body on the CPU; ``tests/test_chip_smoke.py`` additionally
pushes it through the TPU lowering at the serving preset's shapes and at
the benchmark's serving cell's. The engine counts the rows that take the
short tile (``attn_rows_short`` of ``attn_rows``;
``health_snapshot()["short_row_pct"]``, docs/OPS.md).

**The window-bounded form** (``window=``; ``paged_attention_window_q1`` /
``_mq`` to a profile): a layer whose query at position ``i`` attends only
``j > i - window``. The table row is then a RING of ``R`` entries, page
``p`` of the slot's positions at entry ``p % R`` (the engine keeps ``R``
blocks a sequence for such a layer, whatever its length), and the loop
over cells starts at the page of the first query's lowest position and
ends at the last query's: a decode slot copies ``window / bs + 1`` pages
and never the context before them. The window's lower edge inside the
first cell and the causal edge inside the last are masks; values behind
the first query's window are zeroed like those past the last position.
One kernel body: the bound is a static argument.

**The latent form** (:func:`paged_attention_latent`, a model whose cache
holds one compressed vector a token a layer, multi-head latent attention
in its absorbed form): ONE pool ``[L, N, bs, D]`` whose page is key and
value at once (lanes ``0..R-1`` the compressed vector, which is the key's
first part AND the value; lanes ``R..R+Dr-1`` the rotary key all heads
share; ``D`` is ``R + Dr`` rounded up to whole 128-lane tiles, because a
kernel's own copy slices HBM in whole tiles and XLA lays the array out so
in any case), one kv head, and every query head of a lane as the row tile. Its grid is the
query LANES (a decode slot is one lane, a prefill chunk one lane a
token), each with the table row it reads and the length it attends, so
its work follows the real tokens like the forms above: a lane of length 0
copies nothing, computes nothing and returns zeros. It shares the tiling
rule, the in-kernel page copies, ``_scores``/``_weighted_values`` and the
online softmax with them, and takes the layer as an operand so that the
pool is never sliced.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret as _interpret

__all__ = ["paged_attention", "paged_attention_latent"]

_NEG_INF = -1e30
# fp32 operands contract in fp32: the MXU's default single bf16 pass would
# round them to 8 mantissa bits, which the CPU interpret-mode parity suites
# never see — the chip must compute the function the tests pin. bf16
# operands need no such care (see ``_scores`` / ``_weighted_values``).
_F32 = jax.lax.Precision.HIGHEST
_LANES = 128
_KV_TILE = 256        # KV positions a cell attends, about
_LATENT_KV_TILE = 128  # the latent form's: never read at 256 (PERF.md §7)
_MAX_PAGES = 16       # pages a cell holds at most (its copies are unrolled)
_ROW_TILE = 128       # query rows a sub-tile of a chunk row holds, at most
# what a profile calls the kernel: [window-bounded?][multi-query?]
_NAMES = (("paged_attention_q1", "paged_attention_mq"),
          ("paged_attention_window_q1", "paged_attention_window_mq"))


def _sublanes(dtype) -> int:
    """Rows of one vector tile of ``dtype``: 8 at 4 bytes, 16 at 2, 32
    at 1 — the alignment a static slice of a tile's rows must keep."""
    return 32 // jnp.dtype(dtype).itemsize


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiling(bs, W, G, Q, q_dtype, kv_tile=_KV_TILE):
    """The three tile sizes, from the shapes alone. ``P`` pages a cell,
    so a cell attends about ``kv_tile`` KV positions; ``R0`` query
    rows for a row that carries ONE query position (its ``G`` grouped
    heads padded to the query dtype's sublane tile); ``TQ`` query rows a
    sub-tile of a longer row — the largest aligned divisor of ``Q * G``
    up to ``_ROW_TILE``, or the whole row where there is none."""
    P = max(1, min(kv_tile // bs, _MAX_PAGES, W))
    QG = Q * G
    sub = _sublanes(q_dtype)
    R0 = min(_round_up(G, sub), QG)
    TQ = next((d for d in range(min(_ROW_TILE, QG), R0 - 1, -1)
               if QG % d == 0 and d % sub == 0), QG)
    return P, R0, TQ


def _scores(q, k):
    """``q [R, D] · k [C, D]ᵀ`` in fp32. Two bf16 operands go through the
    MXU as they are, ONE pass: a bf16 x bf16 product is exact in fp32 and
    the accumulator is fp32, so it is the same sum as ``HIGHEST`` over
    the cast operands. fp32 operands contract in fp32."""
    precision = None if q.dtype == k.dtype == jnp.bfloat16 else _F32
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _weighted_values(p, v):
    """``p [R, C] · v [C, D]`` with the softmax weights at their full fp32
    width. Against bf16 values the weights split into three bf16 terms
    (``p = hi + mid + lo``, 24 bits) and each meets ``v`` in one exact
    pass — three passes for the six ``HIGHEST`` spends splitting a ``v``
    that has nothing below its first term."""
    dn = (((1,), (0,)), ((), ()))
    if v.dtype != jnp.bfloat16:
        return jax.lax.dot_general(p, v, dn, precision=_F32,
                                   preferred_element_type=jnp.float32)
    out = None
    for _ in range(3):
        term = p.astype(jnp.bfloat16)
        part = jax.lax.dot_general(term, v, dn,
                                   preferred_element_type=jnp.float32)
        out = part if out is None else out + part
        p = p - term.astype(jnp.float32)
    return out


def _kernel(*refs, bs, W, P, scale, quant, layered, Hk, G, Q, R0, TQ,
            kv_dtype, window=None):
    """One grid step = one slot ``m``; inside it a loop over the slot's
    LIVE cells of ``P`` KV pages. The pools stay in HBM: the kernel copies
    a cell's live pages into a two-slot VMEM buffer itself, the next
    cell's while this one computes; per kv head and query tile the cell
    reads its ``P * bs`` rows as ``kv_dtype`` (out of the buffer, or for a
    row of several tiles out of the head-major scratch they were stacked
    into once a cell) and does ONE score matmul, ONE value matmul and ONE
    update of ``m``/``l``/``acc``. An int8 pool's scales arrive laid out
    by cell (``[1, cells, Hk, P * bs]``, positions along lanes) and scale
    the scores' and the weights' COLUMNS: ``(q · kᵀ) * ks`` and ``(p * vs)
    · v`` are the dequantized sums with the int8 values, exact in either
    float type, as operands.

    ``layered``: the pools are every layer's, ``[L, N, Hk, bs, D]``, and
    a last scalar-prefetch operand names the layer whose pages are copied
    (``pool.at[layer, blk]``); nothing else differs.

    ``Q = 1`` is the single-token decode step. ``Q > 1`` is the
    multi-query entry point: a third scalar-prefetch operand carries each
    slot's draft length ``dl`` and the slot runs only the query rows
    ``q <= dl`` — a ``dl == 0`` slot the ``R0``-row tile (exactly the
    decode step's work), a longer one as many ``TQ``-row sub-tiles as
    ``(dl + 1) * G`` rows fill. Query offset ``i`` attends ``j <= sl +
    min(i, dl)``; output rows past ``dl`` are written as zeros.

    ``window``: the WINDOW-BOUNDED form. Query offset ``i`` attends only
    ``j > sl + i - window`` as well, and the table row is a RING of ``W``
    entries: page ``p`` of the slot's positions lives at entry ``p % W``.
    The loop over cells starts at the page that holds the first query's
    lowest position, ``max(sl - window + 1, 0)``, and ends at the last
    query's page: nothing before it is copied or computed, and the two
    edges inside the first and last cells are masks."""
    multi = Q > 1
    tbl_ref, sl_ref = refs[:2]
    dl_ref = refs[2] if multi else None
    refs = refs[3 if multi else 2:]
    if layered:
        layer, refs = refs[0][0], refs[1:]
    q_ref, hbm = refs[0], refs[1:3]      # the K and V pools, in HBM
    ks_ref, vs_ref = refs[3:5] if quant else (None, None)
    (o_ref, acc_ref, m_ref, l_ref, qpos_ref, kh_ref, vh_ref, kbuf, vbuf,
     ksem, vsem) = refs[-11:]
    m = pl.program_id(0)
    QG = Q * G
    C = P * bs

    sl = sl_ref[m]
    dl = dl_ref[m] if multi else 0
    # the slot's attendable window is j <= sl + dl: pages and cells past
    # it are never copied and never computed
    if window is None:
        lo, first = 0, 0
        pages = jnp.minimum((sl + dl) // bs + 1, W)
    else:                              # pages [first, first + pages) only
        lo = jnp.maximum(sl - (window - 1), 0)
        first = lo // bs
        pages = jnp.minimum((sl + dl) // bs + 1 - first, W)
    cells = (pages + (P - 1)) // P

    def unrolled(n, body):
        """``body(i)`` for ``i < n``, traced ONCE and unrolled when the
        kernel lowers (each copy sees its index as a constant): a Python
        loop would trace the body ``n`` times, and tracing is what a warm
        start pays for every program that holds this kernel."""
        jax.lax.fori_loop(0, n, lambda i, carry: (body(i), carry)[1], 0,
                          unroll=True)

    def copy_pages(c, slot, go):
        """Start (or wait for) the copies of cell ``c``'s live pages into
        buffer slot ``slot``; one semaphore a pool and slot."""
        def page(i):
            @pl.when(c * P + i < pages)
            def _live():
                col = c * P + i
                if window is not None:
                    col = jax.lax.rem(first + col, W)
                blk = tbl_ref[m, col]
                at = (layer, blk) if layered else (blk,)
                for pool, buf, sem in zip(hbm, (kbuf, vbuf), (ksem, vsem)):
                    go(pltpu.make_async_copy(
                        pool.at[at], buf.at[slot, i], sem.at[slot]))
        unrolled(P, page)

    def head_rows(buf, slot, h):
        """Head ``h``'s rows of the ``P`` pages in buffer slot ``slot``,
        stacked in the cell's order: ``[P * bs, D]`` of ``kv_dtype``. A
        page is ``(Hk, bs, D)``, so the head's rows are ``P`` whole
        ``(bs, D)`` tiles, read at once."""
        x = buf[slot, :, h]                              # [P, bs, D]
        if quant:                      # int8 -> float goes through fp32
            x = x.astype(jnp.float32)
        return x.astype(kv_dtype).reshape(C, x.shape[-1])

    def row_pos(r0, nr):
        """Query offset of each of the ``nr`` tile rows from ``r0`` on
        (row ``q * G + g`` is query offset ``q``), ``[nr, 1]``: rank 2
        like every index vector here (Mosaic has no rank-1 layout)."""
        r = r0 + jax.lax.broadcasted_iota(jnp.int32, (nr, 1), 0)
        return r // G if G > 1 else r

    def init(r0, nr):
        rows = pl.ds(r0, nr)
        acc_ref[:, rows, :] = jnp.zeros((Hk, nr, acc_ref.shape[-1]),
                                        jnp.float32)
        m_ref[:, rows, :] = jnp.full((Hk, nr, 1), _NEG_INF, jnp.float32)
        l_ref[:, rows, :] = jnp.zeros((Hk, nr, 1), jnp.float32)
        if multi:                      # the row's causal edge past ``sl``:
            # once a slot, so that no cell divides a tile of rows by ``G``
            qpos_ref[rows, :] = jnp.minimum(row_pos(r0, nr), dl)

    def attend(c, slot):
        """Fold cell ``c``'s ``C`` KV positions, resident in buffer slot
        ``slot``, into the accumulators of every query row the slot
        fills."""
        base = c * C if window is None else first * bs + c * C
        last = base + (C - 1)

        def attendable():
            """Down sublanes, the cell's positions that SOME query of the
            slot attends. Containment: V at the others must be ZEROED, not
            merely zero-weighted — a poisoned request can park NaN there
            (see llama._masked_sdpa); exact 0.0 weights make this
            bit-invisible for finite KV. The widest window any query row
            reaches is j <= sl + dl (every position there was written this
            dispatch or earlier), so the union can never touch a stale
            block tail; under ``window`` nothing behind the first query's
            lower edge either. A page of the cell that was not copied
            (past ``pages``) holds whatever the buffer held: it lies past
            ``sl + dl`` like any block's stale tail."""
            jcol = base + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
            keep = jcol <= sl + dl
            if window is not None:
                keep &= jcol >= lo
            return keep

        def stack_heads():
            """What the cell and the kv head alone decide, ONCE a cell:
            each head's K and V rows out of the pages into the head-major
            scratch that every sub-tile of the row then reads
            contiguously, V contained. A cell wholly inside ``lo <= j <=
            sl + dl`` has nothing to contain and runs none of it."""
            def head(h):
                kh_ref[h] = head_rows(kbuf, slot, h)
                vh_ref[h] = head_rows(vbuf, slot, h)
            unrolled(Hk, head)
            if quant:                  # the V scales take the zero: below
                return
            outside = last > sl + dl
            if window is not None:
                outside |= base < lo

            @pl.when(outside)
            def _contain():
                keep = attendable()

                def head(h):
                    v = vh_ref[h]
                    vh_ref[h] = jnp.where(keep, v, jnp.zeros_like(v))
                unrolled(Hk, head)

        def tile(r0, nr, stacked):
            """One tile of query rows against the cell. ``stacked``: the
            tile is a sub-tile of a row that has several, and reads the
            heads' rows ``stack_heads`` left; the one tile of a shorter
            row stacks them in registers as it goes."""
            rows = pl.ds(r0, nr)
            # the cell's KV positions along lanes against each row's own
            # edges (per-query-row causal draft window)
            jrow = base + jax.lax.broadcasted_iota(jnp.int32, (nr, C), 1)
            edge = sl + qpos_ref[rows, :] if multi else sl
            valid = jrow <= edge
            if window is not None:
                valid &= jrow > edge - window
            if quant:                  # an int8 value is finite: there it
                # is the V SCALE that can hold the NaN, and takes the zero
                keep = jrow[:1] <= sl + dl
            elif not stacked:
                keep = attendable()

            def head(h):
                q = q_ref[0, h, rows, :].astype(kv_dtype)    # [nr, D]
                if stacked:
                    k, v = kh_ref[h], vh_ref[h]              # [C, D]
                else:
                    k = head_rows(kbuf, slot, h)
                    v = head_rows(vbuf, slot, h)
                    if not quant:
                        v = jnp.where(keep, v, jnp.zeros_like(v))
                s = _scores(q, k) * scale
                if quant:              # dequant: one scale a KV column
                    s = s * ks_ref[0, c, pl.ds(h, 1), :]     # [1, C]
                s = jnp.where(valid, s, _NEG_INF)
                m_prev = m_ref[h, rows, :]               # [nr, 1]
                m_cur = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_cur)
                alpha = jnp.exp(m_prev - m_cur)
                l_ref[h, rows, :] = l_ref[h, rows, :] * alpha + \
                    jnp.sum(p, axis=1, keepdims=True)
                if quant:
                    p = p * jnp.where(keep, vs_ref[0, c, pl.ds(h, 1), :],
                                      0.0)
                acc_ref[h, rows, :] = acc_ref[h, rows, :] * alpha + \
                    _weighted_values(p, v)
                m_ref[h, rows, :] = m_cur
            unrolled(Hk, head)

        over_rows(functools.partial(tile, stacked=False),
                  functools.partial(tile, stacked=True), stack_heads)

    def finalize(r0, nr):
        rows = pl.ds(r0, nr)
        l = l_ref[:, rows, :]
        out = acc_ref[:, rows, :] / jnp.where(l == 0.0, 1.0, l)
        if multi:                      # tile rows past the slot's draft
            real = row_pos(r0, nr) <= dl                 # [nr, 1]
            out = jnp.where(real[None], out, 0.0)
        o_ref[0, :, rows, :] = out.astype(o_ref.dtype)

    def over_rows(fn, sub=None, once=None):
        """Run ``fn(r0, nr)`` over the query tiles this slot fills. A row
        of SEVERAL tiles runs ``once()`` and then ``sub(r0, nr)`` over its
        sub-tiles (``fn`` where no ``sub`` is given)."""
        if R0 == QG:                   # one tile is the whole row
            fn(0, QG)
            return

        pl.when(dl == 0)(lambda: fn(0, R0))

        @pl.when(dl > 0)
        def _chunk():
            if TQ == QG:
                fn(0, QG)
                return
            if once is not None:
                once()
            each = sub or fn

            def tile(t, carry):
                each(pl.multiple_of(t * TQ, TQ), TQ)
                return carry
            jax.lax.fori_loop(0, ((dl + 1) * G + (TQ - 1)) // TQ, tile, 0)

    def cell(c, carry):
        slot = jax.lax.rem(c, 2)
        pl.when(c + 1 < cells)(
            lambda: copy_pages(c + 1, 1 - slot, lambda cp: cp.start()))
        copy_pages(c, slot, lambda cp: cp.wait())
        attend(c, slot)
        return carry

    copy_pages(0, 0, lambda cp: cp.start())
    over_rows(init)
    jax.lax.fori_loop(0, cells, cell, 0)
    if R0 != QG:                       # rows no tile reaches read zero
        o_ref[...] = jnp.zeros_like(o_ref)
    over_rows(finalize)


def _vmem_bytes(Hk, QG, D, bs, C, cells, rows, q_dtype, out_dtype,
                pool_dtype, kv_dtype) -> int:
    """Scoped-VMEM request for one grid step, from the shapes: the query
    and output tiles (and an int8 pool's by-cell scales) are
    double-buffered by the pipeline, each padded to its dtype's vector
    tile; the kernel's own two-slot K and V page buffers of ``C = P * bs``
    positions (``(Hk, bs, D)`` pages: a head's ``bs`` rows padded to the
    pool dtype's tile) and the head-major K and V rows a chunk row stacks
    once a cell; the three accumulators and the rows' query offsets, resident
    (``m``/``l`` and the offsets pad their one column to a 128-lane
    tile); the head loop's temporaries, sized by the widest query tile a
    cell runs (``rows = max(R0, TQ)`` against ``C`` positions) and no
    longer by ``Q``. A mixed step's prefill chunk under GQA needs more
    than the compiler's 16 MiB default for its resident tiles; a decode
    step far less."""
    isz = lambda dt: jnp.dtype(dt).itemsize
    pad = lambda n, dt: _round_up(n, _sublanes(dt))
    lanes = lambda n: _round_up(n, _LANES)
    tiles = 2 * Hk * lanes(D) * (pad(QG, q_dtype) * isz(q_dtype) +
                                 pad(QG, out_dtype) * isz(out_dtype))
    if jnp.dtype(pool_dtype) == jnp.int8:
        tiles += 2 * 2 * cells * pad(Hk, jnp.float32) * lanes(C) * 4
    kv = 2 * 2 * (C // bs) * Hk * pad(bs, pool_dtype) * lanes(D) * \
        isz(pool_dtype)
    kv += 2 * Hk * pad(C, kv_dtype) * lanes(D) * isz(kv_dtype)
    scratch = Hk * pad(QG, jnp.float32) * (lanes(D) + 2 * _LANES) * 4
    scratch += pad(QG, jnp.int32) * _LANES * 4
    rows = pad(rows, jnp.float32)
    temps = 2 * 4 * (rows * (2 * lanes(D) + 6 * lanes(C)) +
                     6 * pad(C, jnp.float32) * lanes(D))
    return max(16 << 20, int(1.25 * (tiles + kv + scratch + temps)))


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                    draft_lens=None, k_scale=None, v_scale=None,
                    scale: Optional[float] = None, out_dtype=None,
                    layer=None, window: Optional[int] = None):
    """Decode attention for ``M`` serving slots straight off the block pool.

    ``q [M, H, D]`` — one query token per slot (the decode entry point) —
    or ``q [M, Q, H, D]`` with ``draft_lens [M]`` — ``Q`` query tokens
    per slot, the MULTI-QUERY entry point (speculative verify, and the
    mixed step's prefill chunks): query offset ``i <= draft_lens[m]`` of
    slot ``m`` sits at KV position ``seq_lens[m] + i`` and attends ``j <=
    seq_lens[m] + i`` (committed KV plus the in-pass draft prefix). Rows
    ``i > draft_lens[m]`` are not computed and come back as ZEROS: no
    caller reads them. ``k_pool``/``v_pool``
    ``[N, Hk, bs, D]`` — ONE layer's physical block pool, head-major (fp,
    or int8 with ``k_scale``/``v_scale [N, Hk, bs]`` fp32
    per-token-per-head scales) — or, told apart by rank, EVERY layer's,
    ``[L, N, Hk, bs, D]`` (scales ``[L, N, Hk, bs]``) with ``layer``, an
    int32 scalar that may be traced: the kernel's page copies index the
    layer, so a caller that holds the whole pool (a layer scan's carry)
    never slices a layer out of it. ``block_tables [M, W]`` int32 — slot
    ``m``'s KV position ``j`` lives in physical block ``block_tables[m, j
    // bs]`` at offset ``j % bs``;
    ``seq_lens [M]`` int32 — slot ``m`` attends positions ``j <=
    seq_lens[m]`` (its new token's KV was just scattered at ``seq_lens[m]``).
    Table entries past a slot's window are never read. Returns
    ``[M, H, D]`` (or ``[M, Q, H, D]``) in ``out_dtype`` (default: the
    pool dtype for fp pools, fp32 for int8 pools — matching the gather
    path's ``_masked_sdpa`` output dtype).

    ``window`` (a static int): the window-bounded form, under its own
    names (``paged_attention_window_q1`` / ``_mq``). Query offset ``i``
    attends ``seq_lens[m] + i - window < j <= seq_lens[m] + i`` and
    ``block_tables [M, R]`` is a RING: position ``j`` lives in block
    ``block_tables[m, (j // bs) % R]``. The caller sizes ``R`` so that the
    pages from the first query's lowest position to the last query's fit
    (``(window + Q) // bs + 2`` entries always do); the kernel copies
    those pages and no other. fp pools only.
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
    layered = k_pool.ndim == 5
    if layered != (layer is not None):
        raise ValueError(
            "paged_attention: a pool of every layer [L, N, Hk, bs, D] is "
            "read at `layer`, one layer's [N, Hk, bs, D] without it; got "
            f"a rank-{k_pool.ndim} pool and layer={layer!r}")
    Hk, bs = k_pool.shape[-3:-1]
    W = block_tables.shape[1]
    if H % Hk:
        raise ValueError(f"paged_attention: {H} query heads not divisible "
                         f"by {Hk} kv heads")
    G = H // Hk
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("paged_attention: k_scale and v_scale must be "
                         "given together")
    if window is not None and quant:
        raise ValueError("paged_attention: the window-bounded form reads "
                         "fp pools only")
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
    P, R0, TQ = _tiling(bs, W, G, Q, q.dtype)
    cells = -(-W // P)
    # the matmuls' operand type, by dtype alone: bf16 queries against a
    # pool whose values bf16 holds exactly (bf16 itself, int8) go through
    # the MXU as bf16 (``_scores``); anything else contracts in fp32
    exact = (q.dtype == jnp.bfloat16 and
             k_pool.dtype in (jnp.bfloat16, jnp.int8))
    kv_dtype = jnp.bfloat16 if exact else jnp.float32
    tbl = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    # scalar-prefetch operands: (tbl, sl) for decode, + dl for multi-query
    # — every index map takes them positionally after the grid indices
    scalars = (tbl, sl, jnp.asarray(draft_lens, jnp.int32)) if multi \
        else (tbl, sl)
    if layered:                        # the kernel indexes the layer
        scalars += (jnp.asarray(layer, jnp.int32).reshape(1),)

    def qmap(m, *_):
        return (m, 0, 0, 0)

    # the query and output tiles' last two dims equal the array's own
    # ((QG, D)): the TPU lowering accepts a full-extent tile at any size.
    # The pools stay where they are (the kernel copies the pages it needs
    # into its own two-slot buffers).
    in_specs = [pl.BlockSpec((1, Hk, QG, D), qmap),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    ops = [qg, k_pool, v_pool]
    if quant:
        # a page's scale tile is (Hk, bs) fp32, narrower than anything a
        # kernel's own copy may slice out of HBM (Mosaic wants 128 lanes),
        # so the scales — 1/32 of the pool's bytes — are gathered here, by
        # table, into the layout a cell reads: [M, cells, Hk, P * bs]
        whole_cells = jnp.pad(tbl, ((0, 0), (0, cells * P - W)))

        def by_cell(plane):
            pages = plane[layer, whole_cells] if layered \
                else plane[whole_cells]
            return pages.reshape(M, cells, P, Hk, bs).transpose(
                0, 1, 3, 2, 4).reshape(M, cells, Hk, P * bs)
        in_specs += [pl.BlockSpec((1, cells, Hk, P * bs), qmap)] * 2
        ops += [by_cell(k_scale), by_cell(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(M,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hk, QG, D), qmap),
        scratch_shapes=[
            pltpu.VMEM((Hk, QG, D), jnp.float32),
            pltpu.VMEM((Hk, QG, 1), jnp.float32),
            pltpu.VMEM((Hk, QG, 1), jnp.float32),
            pltpu.VMEM((QG, 1), jnp.int32),
            pltpu.VMEM((Hk, P * bs, D), kv_dtype),
            pltpu.VMEM((Hk, P * bs, D), kv_dtype),
            pltpu.VMEM((2, P) + k_pool.shape[-3:], k_pool.dtype),
            pltpu.VMEM((2, P) + v_pool.shape[-3:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, W=W, P=P, scale=scale,
                          quant=quant, layered=layered, Hk=Hk, G=G, Q=Q,
                          R0=R0, TQ=TQ, kv_dtype=kv_dtype,
                          window=None if window is None else int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, Hk, QG, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_bytes(Hk, QG, D, bs, P * bs, cells,
                                         max(R0, TQ), q.dtype, out_dtype,
                                         k_pool.dtype, kv_dtype)),
        interpret=_interpret(),
        # the trace and the compiled text name the custom call after this:
        # the decode form and the multi-query (mixed / verify) form are
        # two kernels to a profile, so they carry two names
        # (and the window-bounded form two more)
        name=_NAMES[window is not None][multi],
    )(*scalars, *ops)
    if multi:
        return out.reshape(M, Hk, Q, G, D).transpose(0, 2, 1, 3, 4) \
                  .reshape(M, Q, H, D)
    return out.reshape(M, H, D)


def _latent_kernel(tbl_ref, slot_ref, len_ref, layer_ref, ql_ref, qr_ref,
                   pool, o_ref, acc_ref, m_ref, l_ref, buf, sem, *, bs, W, P,
                   R, scale, kv_dtype):
    """One grid step = one query lane ``t``: its ``H`` heads are the row
    tile, its table row is ``slot[t]``, it attends ``j < len[t]``. The
    pool stays in HBM; a cell's live pages of layer ``layer`` are copied
    into a two-slot buffer of ``P * bs`` positions, the next cell's while
    this one computes. A page is ``[bs, D]``: lanes ``0..R-1`` the
    compressed vector (key part AND value), the next ``Dr`` the shared
    rotary key, the rest padding. Scores are ``q_lat . c + q_rope . k_rope``; one online-softmax
    update a cell."""
    t = pl.program_id(0)
    n = len_ref[t]
    row = slot_ref[t]
    layer = layer_ref[0]
    pages = jnp.minimum((n + (bs - 1)) // bs, W)
    cells = (pages + (P - 1)) // P
    C = P * bs
    H = ql_ref.shape[1]

    def copy_pages(c, slot, go):
        def page(i, carry):
            @pl.when(c * P + i < pages)
            def _live():
                blk = tbl_ref[row, c * P + i]
                go(pltpu.make_async_copy(
                    pool.at[layer, blk], buf.at[slot, pl.ds(i * bs, bs)],
                    sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, P, page, 0, unroll=True)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def cell(c, carry):
        slot = jax.lax.rem(c, 2)
        pl.when(c + 1 < cells)(
            lambda: copy_pages(c + 1, 1 - slot, lambda cp: cp.start()))
        copy_pages(c, slot, lambda cp: cp.wait())
        base = c * C
        jcol = base + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        jrow = base + jax.lax.broadcasted_iota(jnp.int32, (H, C), 1)
        lat = buf[slot, :, pl.ds(0, R)].astype(kv_dtype)         # [C, R]
        rot = buf[slot, :, pl.ds(R, qr_ref.shape[-1])].astype(kv_dtype)
        # containment, as in the forms above: what no query may attend
        # (a page never copied, a block's stale tail) is zeroed where it
        # is a VALUE; a score there is replaced below
        lat = jnp.where(jcol < n, lat, jnp.zeros_like(lat))
        s = (_scores(ql_ref[0].astype(kv_dtype), lat) +
             _scores(qr_ref[0].astype(kv_dtype), rot)) * scale
        s = jnp.where(jrow < n, s, _NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _weighted_values(p, lat)
        m_ref[...] = m_cur
        return carry

    copy_pages(0, 0, lambda cp: cp.start())
    jax.lax.fori_loop(0, cells, cell, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def paged_attention_latent(q_lat, q_rope, pool, layer, block_tables,
                           lane_slot, lane_len, scale: float,
                           out_dtype=None):
    """Attention of ``T`` query lanes straight off a LATENT block pool.

    ``q_lat [T, H, R]`` (each head's query carried into the compressed
    space) and ``q_rope [T, H, Dr]``; ``pool [L, N, bs, D]``, ``D >= R +
    Dr``, every layer's pool, of which layer ``layer`` (an int32 scalar,
    traced) is read: position ``j`` of table row ``s`` is ``pool[layer,
    block_tables[s, j // bs], j % bs]``, lanes ``0..R-1`` the compressed
    vector and the next ``Dr`` the rotary key all heads share. Lane ``t`` reads
    table row ``lane_slot[t]`` and attends ``j < lane_len[t]``; a lane of
    length 0 is not computed and returns zeros. Returns ``ctx [T, H, R]``:
    ``softmax((q_lat . c + q_rope . k_rope) * scale) . c``."""
    T, H, R = q_lat.shape
    _, N, bs, D = pool.shape
    W = block_tables.shape[1]
    Dr = q_rope.shape[-1]
    if q_rope.shape != (T, H, Dr) or R + Dr > D:
        raise ValueError(f"paged_attention_latent: q_rope {q_rope.shape} "
                         f"against a pool of width {D} and q_lat of {R}")
    if out_dtype is None:
        out_dtype = pool.dtype
    P, _, _ = _tiling(bs, W, H, 1, q_lat.dtype, _LATENT_KV_TILE)
    exact = q_lat.dtype == pool.dtype == jnp.bfloat16
    kv_dtype = jnp.bfloat16 if exact else jnp.float32
    scalars = (jnp.asarray(block_tables, jnp.int32),
               jnp.asarray(lane_slot, jnp.int32),
               jnp.asarray(lane_len, jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1))

    def lane(t, *_):
        return (t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(T,),
        in_specs=[pl.BlockSpec((1, H, R), lane),
                  pl.BlockSpec((1, H, Dr), lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, R), lane),
        scratch_shapes=[
            pltpu.VMEM((H, R), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((2, P * bs, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, W=W, P=P, R=R,
                          scale=float(scale), kv_dtype=kv_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, R), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="paged_attention_latent",
    )(*scalars, q_lat, q_rope, pool)
