"""Tensor-parallel primitive ops.

Parity target: ``python/paddle/distributed/fleet/layers/mpu/mp_ops.py`` in the
reference (``_c_identity``, ``_mp_allreduce``, ``_c_split``, ``_c_concat`` — thin
wrappers over NCCL collectives with custom autograd rules). TPU redesign: every
primitive has TWO lowerings selected at trace time:

* **GSPMD path** (eager or plain ``jit`` over a mesh): the logical value is the
  FULL tensor; the primitive is a ``sharding constraint`` (XLA inserts the
  all-gather/psum and derives the transposed collective for the backward pass).
  This is the idiomatic TPU form — no hand-written comm, exact serial numerics.
* **shard_map path** (inside an explicitly-partitioned region, e.g. a pipeline
  stage body): values are per-rank local shards, and the primitive emits the raw
  ``lax`` collective with a ``jax.custom_vjp`` implementing the Megatron-style
  forward/backward pairing (identity/psum, psum/identity, split/gather, ...).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size
from jax.sharding import NamedSharding, PartitionSpec as P

from .....core.tensor import Tensor
from .....ops._helpers import ensure_tensor, forward_op
from ....collective import _axis_bound
from ....topology import get_hybrid_communicate_group

__all__ = ["c_identity", "mp_allreduce", "c_split", "c_concat", "c_constrain",
           "in_mp_region", "mp_axis_size", "mp_axis_name"]

_MP_AXIS = "mp"


def mp_axis_name(group=None) -> str:
    if group is None:
        return _MP_AXIS
    if isinstance(group, str):
        return group
    name = getattr(group, "name", None)
    if isinstance(name, str):
        return name
    raise TypeError(f"unsupported mp group: {group!r}")


def in_mp_region(axis: str = _MP_AXIS) -> bool:
    """True under a shard_map trace with the mp axis bound."""
    return _axis_bound(axis)


def mp_axis_size(axis: str = _MP_AXIS) -> int:
    hcg = get_hybrid_communicate_group()
    return int(hcg.mesh.shape.get(axis, 1))


def _mesh():
    return get_hybrid_communicate_group().mesh


def _put(val, spec: P):
    """Apply a sharding constraint to a raw jax value: with_sharding_constraint
    under a trace, device_put on concrete arrays (eager)."""
    sharding = NamedSharding(_mesh(), spec)
    if isinstance(val, jax.core.Tracer):
        return lax.with_sharding_constraint(val, sharding)
    return jax.device_put(val, sharding)


def _last_dim_spec(ndim: int, axis: str) -> P:
    return P(*([None] * (ndim - 1) + [axis]))


# -- custom-vjp raw collectives for the shard_map path -----------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _identity_psum_bwd(x, axis):
    return x


def _ipb_fwd(x, axis):
    return x, None


def _ipb_bwd(axis, _, g):
    return (lax.psum(g, axis),)


_identity_psum_bwd.defvjp(_ipb_fwd, _ipb_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_identity_bwd(x, axis):
    return lax.psum(x, axis)


def _pib_fwd(x, axis):
    return lax.psum(x, axis), None


def _pib_bwd(axis, _, g):
    return (g,)


_psum_identity_bwd.defvjp(_pib_fwd, _pib_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _split_dim(x, axis, dim):
    """Slice this rank's chunk along ``dim`` / backward all-gather."""
    n = axis_size(axis)
    me = lax.axis_index(axis)
    piece = x.shape[dim] // n
    return lax.dynamic_slice_in_dim(x, me * piece, piece, axis=dim)


def _split_fwd(x, axis, dim):
    return _split_dim(x, axis, dim), None


def _split_bwd(axis, dim, _, g):
    return (lax.all_gather(g, axis, axis=dim, tiled=True),)


_split_dim.defvjp(_split_fwd, _split_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _concat_dim(x, axis, dim):
    """All-gather along ``dim`` / backward slice this rank's chunk."""
    return lax.all_gather(x, axis, axis=dim, tiled=True)


def _concat_fwd(x, axis, dim):
    return _concat_dim(x, axis, dim), None


def _concat_bwd(axis, dim, _, g):
    n = axis_size(axis)
    me = lax.axis_index(axis)
    piece = g.shape[dim] // n
    return (lax.dynamic_slice_in_dim(g, me * piece, piece, axis=dim),)


_concat_dim.defvjp(_concat_fwd, _concat_bwd)


def _split_last(x, axis):
    return _split_dim(x, axis, x.ndim - 1)


def _concat_last(x, axis):
    return _concat_dim(x, axis, x.ndim - 1)


# -- public primitives -------------------------------------------------------

def c_identity(t, group=None):
    """Identity forward / mp-allreduce backward (enters a ColumnParallel region).

    GSPMD path: pure identity — XLA derives the grad reduction from the weight
    sharding, so no constraint is needed.
    """
    axis = mp_axis_name(group)
    t = ensure_tensor(t)
    if in_mp_region(axis):
        return forward_op("c_identity", lambda x: _identity_psum_bwd(x, axis), [t])
    return t


def mp_allreduce(t, group=None):
    """mp-allreduce forward / identity backward (exits a RowParallel region)."""
    axis = mp_axis_name(group)
    t = ensure_tensor(t)
    if in_mp_region(axis):
        return forward_op("mp_allreduce", lambda x: _psum_identity_bwd(x, axis), [t])
    # GSPMD: the partial-sum contraction was already reduced by XLA; this is a
    # replication constraint at most
    return forward_op("mp_allreduce", lambda x: _put(x, P()), [t])


def c_split(t, group=None):
    """Split the last dim over the mp axis (rank r takes chunk r)."""
    axis = mp_axis_name(group)
    t = ensure_tensor(t)
    if in_mp_region(axis):
        return forward_op("c_split", lambda x: _split_last(x, axis), [t])
    return forward_op(
        "c_split", lambda x: _put(x, _last_dim_spec(t.ndim, axis)), [t])


def c_concat(t, group=None):
    """Concatenate the last dim over the mp axis (all-gather)."""
    axis = mp_axis_name(group)
    t = ensure_tensor(t)
    if in_mp_region(axis):
        return forward_op("c_concat", lambda x: _concat_last(x, axis), [t])
    return forward_op("c_concat", lambda x: _put(x, P()), [t])


def c_constrain(t, spec: P):
    """Annotate a tensor with a PartitionSpec (GSPMD hint; no-op in shard_map)."""
    t = ensure_tensor(t)
    names = [n for ax in spec for n in (ax if isinstance(ax, tuple) else (ax,))
             if n is not None]
    if any(_axis_bound(n) for n in names):
        return t
    return forward_op("c_constrain", lambda x: _put(x, spec), [t])
