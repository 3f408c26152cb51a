"""Imperative collective API (``paddle.distributed.all_reduce`` et al).

Parity target: ``python/paddle/distributed/communication/`` over
``ProcessGroupNCCL`` (``paddle/fluid/distributed/collective/``) in the reference.
TPU redesign: there is no NCCL — a collective is an XLA HLO op on a named mesh
axis, compiled and run over ICI. The single-controller encoding of "each rank holds
its own tensor" is an array with a leading rank dimension sharded over the group's
axis; each collective is a cached jit(shard_map(lax_collective)). Inside an
already-sharded region (shard_map / pjit trace), the same functions emit the raw
``lax.psum``-family op directly — the façade the reference reaches via
process_group dispatch.

Group argument: a ``ParallelAxis`` (from topology), an axis name string, or None
(default = the whole default mesh flattened).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, _wrap_value
from ..health import watchdog
from ..ops._helpers import ensure_tensor, forward_op
from .topology import ParallelAxis, get_hybrid_communicate_group

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "alltoall", "broadcast",
           "reduce", "scatter", "barrier", "ReduceOp", "get_rank",
           "get_world_size", "is_initialized", "init_parallel_env",
           "in_shard_region"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_initialized = False


def init_parallel_env():
    """Bootstrap (``paddle.distributed.init_parallel_env`` parity). Multi-host
    initialization goes through jax.distributed (the coordination service is the
    TCPStore equivalent); single-host is a no-op beyond marking initialized."""
    global _initialized
    import os

    # elastic liveness: stamp heartbeats into the launcher's TCPStore so a
    # hung (not just crashed) worker is detected (distributed/elastic.py)
    if os.environ.get("PADDLE_ELASTIC_STORE"):
        from .elastic import start_heartbeat
        start_heartbeat()
    if not _initialized and os.environ.get("PADDLE_TRAINERS_NUM", "1") not in ("", "1"):
        # multi-host: consume the launcher's env contract (launch/main.py)
        # explicitly — jax.distributed's own autodetect doesn't know the
        # PADDLE_* names; the coordination service is the TCPStore equivalent
        coord = os.environ.get("PADDLE_DIST_COORDINATOR")
        kwargs = {}
        if coord:
            kwargs = dict(
                coordinator_address=coord,
                num_processes=int(os.environ["PADDLE_DIST_NUM_PROCESSES"]),
                process_id=int(os.environ["PADDLE_DIST_PROCESS_ID"]))
        jax.distributed.initialize(**kwargs)
    _initialized = True
    return None


def is_initialized() -> bool:
    return _initialized


def get_rank(group=None) -> int:
    return jax.process_index()


def get_world_size(group=None) -> int:
    axis = _resolve_axis(group)
    return axis.nranks if axis is not None else jax.device_count()


def in_shard_region() -> bool:
    """True when called under a shard_map/pjit trace with mesh axes bound."""
    return _axis_bound(_resolve_axis(None).name)


def _resolve_axis(group) -> Optional[ParallelAxis]:
    if isinstance(group, ParallelAxis):
        return group
    hcg = get_hybrid_communicate_group()
    if group is None:
        # default group = the whole world: every non-trivial mesh axis (the
        # reference's global default process group; spanning one axis only when
        # one is non-trivial keeps specs simple in the common pure-dp case)
        live = tuple(a for a in hcg.mesh.axis_names
                     if hcg.degrees.get(a, 1) > 1)
        if not live:
            return ParallelAxis(hcg.mesh, "dp")
        return ParallelAxis(hcg.mesh, live[0] if len(live) == 1 else live)
    if isinstance(group, str):
        return ParallelAxis(hcg.mesh, group)
    if isinstance(group, (tuple, list)):
        return ParallelAxis(hcg.mesh, tuple(group))
    raise TypeError(f"unsupported group: {group!r}")


def _axis_bound(name) -> bool:
    names = name if isinstance(name, tuple) else (name,)
    try:
        for a in names:
            lax.axis_index(a)
        return True
    except NameError:  # "unbound axis name" — not inside shard_map/pjit
        return False


@functools.lru_cache(maxsize=256)
def _compiled_collective(op: str, mesh: Mesh, axis, shape, dtype, extra=None):
    def body(x):
        # x is the local shard [1, ...] (one row of the per-rank encoding)
        if op == "all_reduce_sum":
            return lax.psum(x, axis)
        if op == "all_reduce_max":
            return lax.pmax(x, axis)
        if op == "all_reduce_min":
            return lax.pmin(x, axis)
        if op == "all_reduce_avg":
            return lax.pmean(x, axis)
        if op == "all_reduce_prod":
            # exact for any sign/zero: gather the factors, multiply locally
            # (reference NCCL prod semantics; log/exp would NaN on negatives)
            g = lax.all_gather(x, axis, axis=0, tiled=True)
            return jnp.prod(g, axis=0, keepdims=True)
        if op == "all_gather":
            return lax.all_gather(x[0], axis, axis=0, tiled=True)[None]
        if op == "reduce_scatter":
            return lax.psum_scatter(x[0], axis, scatter_dimension=0,
                                    tiled=True)[None]
        if op == "alltoall":
            return lax.all_to_all(x[0], axis, split_axis=0, concat_axis=0,
                                  tiled=True)[None]
        if op == "broadcast":
            src = extra
            me = lax.axis_index(axis)
            return lax.psum(jnp.where(me == src, x, jnp.zeros_like(x)), axis)
        raise ValueError(op)

    spec = P(axis)
    fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.jit(fn)


def _per_rank(value, axis: ParallelAxis):
    """Validate + shard the leading rank dimension over the axis."""
    n = axis.nranks
    if value.shape[0] != n:
        raise ValueError(
            f"collective input must have leading rank dim {n} (the "
            f"single-controller per-rank encoding), got shape {value.shape}")
    sharding = NamedSharding(axis.mesh, P(axis.name))
    return jax.device_put(value, sharding)


def _run_collective(op: str, t, group, extra=None, differentiable=True):
    t = ensure_tensor(t)
    axis = _resolve_axis(group)
    # a rank frozen here is the classic alive-but-hung failure: the section
    # marker lets the hang watchdog's diagnosis name the collective (and
    # the heartbeat watchdog name the rank) instead of reporting a generic
    # stall (health.watchdog; no-op unless a watchdog is installed)
    with watchdog.section(f"collective:{op}"):
        if _axis_bound(axis.name):
            # in-graph path: emit the raw collective on the bound axis
            return forward_op(op, lambda x: _ingraph(op, x, axis.name, extra),
                              [t], differentiable=differentiable)
        fn = _compiled_collective(op, axis.mesh, axis.name, None, None, extra)

        def impl(x):
            return fn(_per_rank(x, axis))

        return forward_op(op, impl, [t], differentiable=differentiable)


def _ingraph(op, x, axis, extra):
    if op == "all_reduce_sum":
        return lax.psum(x, axis)
    if op == "all_reduce_max":
        return lax.pmax(x, axis)
    if op == "all_reduce_min":
        return lax.pmin(x, axis)
    if op == "all_reduce_avg":
        return lax.pmean(x, axis)
    if op == "all_reduce_prod":
        return jnp.prod(lax.all_gather(x, axis, axis=0, tiled=False), axis=0)
    if op == "all_gather":
        return lax.all_gather(x, axis, axis=0, tiled=True)
    if op == "reduce_scatter":
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if op == "alltoall":
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
    if op == "broadcast":
        me = lax.axis_index(axis)
        return lax.psum(jnp.where(me == extra, x, jnp.zeros_like(x)), axis)
    raise ValueError(op)


# -- public API -------------------------------------------------------------

def all_reduce(tensor, op: str = ReduceOp.SUM, group=None, sync_op: bool = True):
    name = {ReduceOp.SUM: "all_reduce_sum", ReduceOp.MAX: "all_reduce_max",
            ReduceOp.MIN: "all_reduce_min", ReduceOp.AVG: "all_reduce_avg",
            ReduceOp.PROD: "all_reduce_prod"}[op]
    out = _run_collective(name, tensor, group)
    if isinstance(tensor, Tensor):
        tensor._rebind(out)
        return tensor
    return out


def all_gather(tensor_or_list, tensor=None, group=None, sync_op: bool = True):
    """paddle two-call-convention parity: all_gather(out_list, t) appends each
    rank's tensor; all_gather(t) returns the gathered Tensor. In the per-rank
    encoding the r-th gathered piece is row r of the input."""
    if isinstance(tensor_or_list, list) and tensor is not None:
        t = ensure_tensor(tensor)
        for r in range(get_world_size(group)):
            tensor_or_list.append(t[r])
        return tensor_or_list
    return _run_collective("all_gather", tensor_or_list, group)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op: bool = True):
    return _run_collective("reduce_scatter", tensor, group)


def alltoall(in_tensor_or_list, out_tensor_list=None, group=None,
             sync_op: bool = True):
    return _run_collective("alltoall", in_tensor_or_list, group)


def broadcast(tensor, src: int = 0, group=None, sync_op: bool = True):
    out = _run_collective("broadcast", tensor, group, extra=int(src))
    if isinstance(tensor, Tensor):
        tensor._rebind(out)
        return tensor
    return out


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM, group=None, sync_op=True):
    # single-controller: reduce == all_reduce (every shard sees the result)
    return all_reduce(tensor, op, group)


def scatter(tensor, tensor_list=None, src: int = 0, group=None, sync_op=True):
    """Scatter ``tensor_list[r]`` to rank r (paddle convention: out arg first).

    Single-controller encoding: the result is the per-rank stack — row r is what
    rank r receives. With no ``tensor_list``, ``tensor`` is the full value held
    by ``src`` and is split evenly along dim 0 into per-rank rows.
    """
    axis = _resolve_axis(group)
    n = axis.nranks
    sharding = NamedSharding(axis.mesh, P(axis.name))
    if tensor_list is not None:
        if len(tensor_list) != n:
            raise ValueError(
                f"scatter: tensor_list has {len(tensor_list)} entries but the "
                f"group has {n} ranks")
        parts = [ensure_tensor(t) for t in tensor_list]
        out = forward_op(
            "scatter",
            lambda *xs: jax.device_put(jnp.stack(xs, axis=0), sharding),
            parts)
    else:
        t = ensure_tensor(tensor)
        if t.shape[0] % n != 0:
            raise ValueError(
                f"scatter: leading dim {t.shape[0]} not divisible by group "
                f"size {n}")
        new_shape = (n, t.shape[0] // n) + tuple(t.shape[1:])
        out = forward_op(
            "scatter",
            lambda x: jax.device_put(x.reshape(new_shape), sharding), [t])
    if isinstance(tensor, Tensor):
        tensor._rebind(out)
        return tensor
    return out


def barrier(group=None):
    """Device-level barrier: block until all pending device work completes."""
    with watchdog.section("collective:barrier"):
        jnp.zeros(()).block_until_ready()
    return None
