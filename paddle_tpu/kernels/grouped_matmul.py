"""Grouped matmul for routed experts: rows sorted by expert, one weight
matrix an expert, only the rows that exist computed.

``grouped_matmul(x [R, K], w [G, K, N], group_sizes [G])`` multiplies the
first ``group_sizes[0]`` rows with ``w[0]``, the next ``group_sizes[1]``
with ``w[1]`` and so on. Rows past ``sum(group_sizes)`` belong to no group
(pairs routed to experts held elsewhere, pad lanes): they are NOT computed
and come back as zeros. An expert with no rows costs nothing: its weights
are never read.

The kernel (TPU; interpret mode elsewhere) walks VISITS: one visit is one
(row tile, group) pair that shares at least a row, in row order, so a row
tile's visits are consecutive and its output block stays resident across
them, each visit writing only its own group's rows. The number of visits
is data (``<= row tiles + G - 1``); the grid is that static bound and a
visit past the last live one is skipped with its block indices clamped to
the last live visit's, so it moves no data. The contraction is tiled
(``tk`` rows of the weight a step, whole ``N`` wide: each step's weight
copy is one contiguous slab) and accumulated in fp32. At a few rows an
expert, the serving regime, a visit costs its weight stream.

``layer=`` (an int32 scalar, traced) reads ``w`` as every layer's experts
stacked, ``[L, G, K, N]``, and multiplies with layer ``layer``'s: the
kernel's weight copies index the stack themselves, so a scan over layers
never slices (copies) a layer's experts out of it. On the chip that slice
cost as much as the matmul it fed (PERF.md, PR 27).

``gated=True`` reads ``w`` as ``[gate | up]`` (``N = 2 I``) and returns
``silu(x w_gate) * (x w_up)`` ``[R, I]``: the activation rides the last
contraction step, so the ``[R, 2 I]`` intermediate never exists.

``use_kernel=False`` is the XLA-composed path (``jax.lax.ragged_dot``),
the oracle the kernel is tested against and what the CPU runs by default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret as _interpret

__all__ = ["grouped_matmul", "visit_plan"]

_ROW_TILE = 128
_SLAB_BYTES = 8 << 20          # one contraction step's weight copy, about


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _contraction_tile(K: int, N: int, itemsize: int) -> int:
    """Rows of the weight a step: the largest divisor of ``K`` that is a
    multiple of 128 and keeps the slab near ``_SLAB_BYTES``; ``K`` itself
    where it has none (small shapes)."""
    best = None
    for tk in range(128, K + 1, 128):
        if K % tk == 0 and tk * N * itemsize <= _SLAB_BYTES:
            best = tk
    return best or K


def visit_plan(group_sizes, tm: int, row_tiles: int):
    """``(group, tile, starts, ends, live)`` of the walk over ``row_tiles``
    tiles of ``tm`` sorted rows: visit ``v < live`` multiplies the rows of
    tile ``tile[v]`` that lie in ``[starts[g], ends[g])``, ``g =
    group[v]``. Visits past ``live`` repeat the last live one's indices."""
    sizes = jnp.asarray(group_sizes, jnp.int32)
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    live = visit_ends[-1]
    v = jnp.minimum(jnp.arange(row_tiles + G - 1, dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                        G - 1).astype(jnp.int32)
    first = visit_ends - tiles
    tile = starts[group] // tm + (v - first[group])
    tile = jnp.clip(tile, 0, row_tiles - 1).astype(jnp.int32)
    return group, tile, starts, ends, live.reshape(1)


def _kernel(group_ref, tile_ref, start_ref, end_ref, live_ref, base_ref,
            x_ref, w_ref, o_ref, acc_ref, *, tm, nk, gated):
    v, k = pl.program_id(0), pl.program_id(1)

    @pl.when(v < live_ref[0])
    def _visit():
        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _store():
            g = group_ref[v]
            rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0)
            mine = (rows >= start_ref[g]) & (rows < end_ref[g])
            out = acc_ref[...]
            if gated:
                half = out.shape[1] // 2
                out = jax.nn.silu(out[:, :half]) * out[:, half:]
            o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


def grouped_matmul(x, w, group_sizes, *, layer=None, gated: bool = False,
                   use_kernel: bool = False, out_dtype=None):
    """See the module's docstring. ``x [R, K]``, ``w [G, K, N]`` (or ``[L,
    G, K, N]`` with ``layer``), ``group_sizes [G]`` int32 -> ``[R, N]``
    (``[R, N // 2]`` gated)."""
    R, K = x.shape
    G, _, N = w.shape[-3:]
    if (layer is None) != (w.ndim == 3):
        raise ValueError("grouped_matmul: layer= goes with w [L, G, K, N]")
    # group g's weights are entry base + g of the experts laid end to end
    base = (jnp.zeros((1,), jnp.int32) if layer is None
            else (jnp.asarray(layer, jnp.int32) * G).reshape(1))
    out_dtype = out_dtype or x.dtype
    No = N // 2 if gated else N
    sizes = jnp.asarray(group_sizes, jnp.int32)
    total = sizes.sum()
    if not use_kernel:
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        out = jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                                 preferred_element_type=jnp.float32)
        if gated:
            out = jax.nn.silu(out[:, :No]) * out[:, No:]
    else:
        tm = min(_ROW_TILE, _round_up(R, 16))
        Rp = _round_up(R, tm)
        if Rp != R:
            x = jnp.pad(x, ((0, Rp - R), (0, 0)))
        tk = _contraction_tile(K, N, w.dtype.itemsize)
        nk = K // tk
        plan = visit_plan(sizes, tm, Rp // tm) + (base,)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(Rp // tm + G - 1, nk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda v, k, g, t, *_: (t[v], k)),
                pl.BlockSpec((1, tk, N), lambda v, k, g, t, *rest:
                             (rest[-1][0] + g[v], k, 0)),
            ],
            out_specs=pl.BlockSpec((tm, No), lambda v, k, g, t, *_: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((tm, N), jnp.float32)],
        )
        vmem = (2 * tk * N * w.dtype.itemsize + 2 * tm * tk * x.dtype.itemsize
                + 3 * tm * N * 4 + 2 * tm * No * jnp.dtype(out_dtype).itemsize)
        out = pl.pallas_call(
            functools.partial(_kernel, tm=tm, nk=nk, gated=gated),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Rp, No), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=max(16 << 20, int(1.25 * vmem))),
            interpret=_interpret(),
            # the name the device trace shows; the gated (gate and up) and
            # the plain (down) call are two kernels to a profile
            name="moe_grouped_matmul_gated" if gated else "moe_grouped_matmul",
        )(*plan, x, w.reshape(-1, K, N))[:R]
    # rows of no group were never written (the kernel) or are the
    # backend's to define (ragged_dot): zeros, by position
    live = jnp.arange(R, dtype=jnp.int32)[:, None] < total
    return jnp.where(live, out, 0).astype(out_dtype)
