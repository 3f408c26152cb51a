"""Context parallelism: Ulysses (sep alltoall) + ring flash attention.

Parity target (SURVEY §5 long-context, §2.5 CP): the reference's ``sep``
axis in ``HybridCommunicateGroup`` with Ulysses-style alltoall head<->seq
swaps and ring flash attention (PaddleNLP
``transformers/ring_flash_attention.py`` — K/V blocks rotated among cp ranks
with online-softmax lse merging). TPU redesign:

* **Ulysses** — ``lax.all_to_all`` on the ``sep`` mesh axis swaps the
  sequence shard for a head shard before attention and back after; one
  compiled collective each way, riding ICI.
* **Ring attention** — ``lax.ppermute`` rotates K/V shards around the sep
  ring (ICI is a torus — ring-native); each step computes a block with the
  Pallas flash kernel and merges via the streamed-softmax rule
  ``lse' = logaddexp(lse, lse_b); out' = out*e^{lse-lse'} + out_b*e^{lse_b-lse'}``.
  Causality: the diagonal step runs the causal kernel; earlier blocks are
  fully visible; later blocks are masked out by zero-weighting (lockstep
  SPMD — every rank does the same number of steps). Backward is ``jax.grad``
  straight through the scan + ppermute (the kernel's custom_vjp gives the
  per-block gradients; the transpose of ppermute is the reverse rotation).

Both entry points exist at two levels: raw functions for use INSIDE a
``shard_map`` region (values are per-rank shards) and Tensor-level wrappers
that build the region over the fleet mesh (full logical values in/out).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..core.tensor import Tensor
from ..ops._helpers import ensure_tensor, forward_op
from .collective import _axis_bound
from .topology import get_hybrid_communicate_group

__all__ = ["ulysses_alltoall", "ulysses_attention", "ring_flash_attention",
           "sep_parallel_attention"]


# ---------------------------------------------------------------------------
# raw (inside-shard_map) primitives on [B, S_shard, H, D] values
# ---------------------------------------------------------------------------

def ulysses_alltoall(x, axis_name: str, scatter_dim: int, gather_dim: int):
    """all_to_all: scatter ``scatter_dim`` (must be divisible by the axis
    size), gather ``gather_dim``. The Ulysses head<->seq swap is two of
    these (ref: sep-group alltoall in PaddleNLP)."""
    return lax.all_to_all(x, axis_name, split_axis=scatter_dim,
                          concat_axis=gather_dim, tiled=True)


def _sdpa(q, k, v, causal):
    """jnp attention oracle for the non-kernel path ([B,S,H,D])."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    lse = jax.nn.logsumexp(s, axis=-1)  # [B, H, Sq]
    return out.astype(q.dtype), lse


def _attn_with_lse(q, k, v, causal, use_kernels):
    if use_kernels:
        from ..kernels.flash_attention import flash_attention_with_lse
        return flash_attention_with_lse(q, k, v, causal=causal)
    return _sdpa(q, k, v, causal)


def ulysses_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                      use_kernels: bool = True):
    """Attention over seq-sharded q/k/v [B, S/n, H, D] (inside shard_map).

    alltoall to [B, S, H/n, D], full-sequence attention on the local heads
    (flash kernel), alltoall back. Requires H % axis_size == 0.
    """
    H = q.shape[2]
    n = axis_size(axis_name)
    if H % n:
        raise ValueError(f"ulysses_attention: heads {H} not divisible by "
                         f"sep degree {n}")
    swap = partial(ulysses_alltoall, axis_name=axis_name, scatter_dim=2,
                   gather_dim=1)
    qh, kh, vh = swap(q), swap(k), swap(v)
    out, _ = _attn_with_lse(qh, kh, vh, causal, use_kernels)
    return ulysses_alltoall(out, axis_name, scatter_dim=1, gather_dim=2)


def ring_flash_attention(q, k, v, axis_name: str = "sep",
                         causal: bool = False, use_kernels: bool = True):
    """Ring attention over seq-sharded q/k/v [B, S/n, H, D] (inside
    shard_map). O(S/n) memory per rank; K/V travel the ring via ppermute."""
    n = axis_size(axis_name)
    i = lax.axis_index(axis_name)
    B, L, H, D = q.shape
    perm = [(r, (r + 1) % n) for r in range(n)]

    # step 0: my own block — the causal diagonal
    out0, lse0 = _attn_with_lse(q, k, v, causal, use_kernels)
    lse0 = lse0.astype(jnp.float32)

    def step(carry, s):
        out_acc, lse_acc, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        # after s rotations (s >= 1) rank i holds block j = (i - s) mod n
        out_b, lse_b = _attn_with_lse(q, kc, vc, False, use_kernels)
        lse_b = lse_b.astype(jnp.float32)
        if causal:
            include = (s <= i)  # j < i  <=>  s <= i (for 1 <= s < n)
            lse_b = jnp.where(include, lse_b, -jnp.inf)
        new_lse = jnp.logaddexp(lse_acc, lse_b)
        # weights in [B,H,S] -> broadcast onto [B,S,H,D]
        w_old = jnp.exp(lse_acc - new_lse)
        w_new = jnp.exp(lse_b - new_lse)
        # avoid nan from exp(-inf - -inf)
        w_new = jnp.where(jnp.isneginf(lse_b), 0.0, w_new)

        def bcast(w):
            return jnp.swapaxes(w, 1, 2)[..., None].astype(out_acc.dtype)
        out_acc = out_acc * bcast(w_old) + out_b * bcast(w_new)
        return (out_acc, new_lse, kc, vc), None

    if n == 1:
        return out0
    (out, _, _, _), _ = lax.scan(step, (out0, lse0, k, v),
                                 jnp.arange(1, n))
    return out


# ---------------------------------------------------------------------------
# Tensor-level wrappers (build the shard_map region over the fleet mesh)
# ---------------------------------------------------------------------------

def sep_parallel_attention(q, k, v, causal: bool = False,
                           impl: str = "ring", mesh: Optional[Mesh] = None,
                           axis_name: str = "sep",
                           use_kernels: Optional[bool] = None):
    """Context-parallel attention on FULL logical [B, S, H, D] tensors.

    Shards the sequence over the ``sep`` mesh axis and runs ring flash
    attention (``impl="ring"``) or Ulysses alltoall attention
    (``impl="ulysses"``) as one compiled shard_map program. Inside an
    existing shard_map region the raw primitives are used directly.
    """
    qt, kt, vt = ensure_tensor(q), ensure_tensor(k), ensure_tensor(v)
    if use_kernels is None:
        from ..kernels.dispatch import on_tpu
        use_kernels = on_tpu()
    fn = {"ring": ring_flash_attention,
          "ulysses": ulysses_attention}.get(impl)
    if fn is None:
        raise ValueError(f"impl must be 'ring' or 'ulysses', got {impl!r}")

    if _axis_bound(axis_name):  # already inside a region
        return forward_op(
            f"sep_attention_{impl}",
            lambda a, b, c: fn(a, b, c, axis_name, causal, use_kernels),
            [qt, kt, vt])

    mesh = mesh or get_hybrid_communicate_group().mesh
    n = int(mesh.shape.get(axis_name, 1))
    if n == 1:
        out, _ = _attn_with_lse(qt._value, kt._value, vt._value, causal,
                                use_kernels)
        return forward_op("sep_attention_serial",
                          lambda a, b, c: _attn_with_lse(
                              a, b, c, causal, use_kernels)[0],
                          [qt, kt, vt])
    spec = P(None, axis_name, None, None)

    def region(a, b, c):
        return fn(a, b, c, axis_name, causal, use_kernels)

    shmap = shard_map(region, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
    return forward_op(f"sep_attention_{impl}", shmap, [qt, kt, vt])
