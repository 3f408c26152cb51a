"""Tensor-(model-)parallel layers.

Parity target: ``python/paddle/distributed/fleet/layers/mpu/mp_layers.py`` in the
reference (``VocabParallelEmbedding``, ``ColumnParallelLinear``,
``RowParallelLinear``, ``ParallelCrossEntropy`` — each rank constructs only its
weight shard and communicates by hand over the mp NCCL group). TPU redesign:
the layer owns the FULL logical weight placed with a ``NamedSharding`` over the
``mp`` mesh axis — construction, checkpointing, and numerics are bit-identical
to the serial layer, and XLA/GSPMD inserts the collectives the reference writes
by hand. Inside an explicitly-partitioned ``shard_map`` region the same layers
emit Megatron-style raw collectives (see mp_ops.py), operating on whatever
local shards the region body was handed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size
from jax.sharding import NamedSharding, PartitionSpec as P

from .....core.tensor import Parameter, Tensor
from .....nn import functional as F
from .....nn import initializer as I
from .....nn.layer import Layer
from .....ops._helpers import ensure_tensor, forward_op
from ....topology import get_hybrid_communicate_group
from . import mp_ops
from .mp_ops import _put, c_concat, c_identity, in_mp_region, mp_allreduce, \
    mp_axis_name

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
           "ParallelCrossEntropy"]


def _axis_size(axis: str) -> int:
    mesh = get_hybrid_communicate_group().mesh
    return int(mesh.shape.get(axis, 1))


def _shard_param(p: Parameter, spec: P):
    """Lay the full logical parameter out over the mesh (annotation only)."""
    mesh = get_hybrid_communicate_group().mesh
    p._raw = jax.device_put(p._raw, NamedSharding(mesh, spec))
    p.is_distributed = True
    return p


def _local_shard(t, axis: str, full: int, dim: int):
    """Inside a shard_map region, a normally-constructed layer closes over its
    FULL logical weight (replicated into the trace); slice this rank's chunk
    along ``dim``. A tensor that already has the local size (params handed in
    explicitly through the region's in_specs) passes through untouched."""
    if t is None:
        return None
    if t.shape[dim] != full:
        return t  # already a local shard
    def f(v):
        n = axis_size(axis)
        per = full // n
        start = lax.axis_index(axis) * per
        return lax.dynamic_slice_in_dim(v, start, per, axis=dim)
    return forward_op("mp_local_shard", f, [ensure_tensor(t)])


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over mp.

    ref: mp_layers.py VocabParallelEmbedding (per-rank vocab range + masked
    lookup + allreduce). GSPMD path: full-weight lookup with the weight sharded
    ``P("mp", None)`` — XLA partitions the gather. shard_map path: the Megatron
    masked local lookup + psum.
    """

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self.axis = mp_axis_name(mp_group)
        n = _axis_size(self.axis)
        if num_embeddings % n:
            raise ValueError(
                f"VocabParallelEmbedding: vocab {num_embeddings} not divisible "
                f"by mp degree {n}")
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.world_size = n
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _shard_param(self.weight, P(self.axis, None))

    def forward(self, x):
        if in_mp_region(self.axis):
            w = _local_shard(self.weight, self.axis, self.num_embeddings, 0)

            def local_lookup(ids, wv):
                # wv is this rank's vocab shard [V/n, D]
                n = axis_size(self.axis)
                per = self.num_embeddings // n
                start = lax.axis_index(self.axis) * per
                local = ids - start
                ok = (local >= 0) & (local < per)
                emb = jnp.take(wv, jnp.where(ok, local, 0), axis=0)
                emb = jnp.where(ok[..., None], emb, 0.0)
                return lax.psum(emb, self.axis)
            return forward_op("vocab_parallel_embedding", local_lookup,
                              [ensure_tensor(x), w])
        return F.embedding(x, self.weight)

    def extra_repr(self):
        return f"{self.num_embeddings}, {self.embedding_dim}, mp={self.world_size}"


class ColumnParallelLinear(Layer):
    """Linear with the output dim sharded over mp (ref: ColumnParallelLinear).

    ``gather_output=True`` returns the full [.., out]; ``False`` leaves the
    activation sharded on its last dim (the usual pairing with a following
    RowParallelLinear).
    """

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, gather_output: bool = True,
                 fuse_matmul_bias: bool = False, mp_group=None, name=None):
        super().__init__()
        self.axis = mp_axis_name(mp_group)
        n = _axis_size(self.axis)
        if out_features % n:
            raise ValueError(
                f"ColumnParallelLinear: out_features {out_features} not "
                f"divisible by mp degree {n}")
        self.in_features = in_features
        self.out_features = out_features
        self.world_size = n
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _shard_param(self.weight, P(None, self.axis))
        self.bias = self.create_parameter([out_features], is_bias=True) \
            if has_bias else None
        if self.bias is not None:
            _shard_param(self.bias, P(self.axis))

    def forward(self, x):
        x = c_identity(x, self.axis)
        if in_mp_region(self.axis):
            w = _local_shard(self.weight, self.axis, self.out_features, 1)
            b = _local_shard(self.bias, self.axis, self.out_features, 0)
            y = F.linear(x, w, b)
        else:
            y = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return c_concat(y, self.axis)
        if not in_mp_region(self.axis):
            y = mp_ops.c_constrain(
                y, P(*([None] * (y.ndim - 1) + [self.axis])))
        return y

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features}, "
                f"mp={self.world_size}, gather_output={self.gather_output}")


class RowParallelLinear(Layer):
    """Linear with the input dim sharded over mp (ref: RowParallelLinear).

    ``input_is_parallel=True`` expects the activation already sharded on its
    last dim (from a ColumnParallelLinear with gather_output=False).
    """

    def __init__(self, in_features: int, out_features: int, weight_attr=None,
                 has_bias: bool = True, input_is_parallel: bool = False,
                 fuse_matmul_bias: bool = False, mp_group=None, name=None):
        super().__init__()
        self.axis = mp_axis_name(mp_group)
        n = _axis_size(self.axis)
        if in_features % n:
            raise ValueError(
                f"RowParallelLinear: in_features {in_features} not divisible "
                f"by mp degree {n}")
        self.in_features = in_features
        self.out_features = out_features
        self.world_size = n
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _shard_param(self.weight, P(self.axis, None))
        self.bias = self.create_parameter([out_features], is_bias=True) \
            if has_bias else None  # bias is added AFTER the reduction

    def forward(self, x):
        if in_mp_region(self.axis):
            w = _local_shard(self.weight, self.axis, self.in_features, 0)
            if not self.input_is_parallel:
                x = mp_ops.c_split(x, self.axis)
            y = F.linear(x, w)  # partial sums
            y = mp_allreduce(y, self.axis)
            if self.bias is not None:
                y = y + self.bias
            return y
        # GSPMD: full logical matmul; contraction over the sharded dim makes
        # XLA emit the reduce itself
        if not self.input_is_parallel:
            x = mp_ops.c_constrain(
                x, P(*([None] * (ensure_tensor(x).ndim - 1) + [self.axis])))
        y = F.linear(x, self.weight)
        y = mp_ops.c_constrain(y, P())
        if self.bias is not None:
            y = y + self.bias
        return y

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features}, "
                f"mp={self.world_size}, input_is_parallel={self.input_is_parallel}")


class ParallelCrossEntropy(Layer):
    """Softmax cross-entropy over vocab-sharded logits (ref: ParallelCrossEntropy).

    GSPMD path: numerically the plain CE on the full logical logits (XLA keeps
    the reductions partitioned). shard_map path: the Megatron algorithm — psum
    of local max / local exp-sums / masked target-logit lookup.
    """

    def __init__(self, mp_group=None, name=None, ignore_index: int = -100):
        super().__init__()
        self.axis = mp_axis_name(mp_group)
        self.ignore_index = ignore_index

    def forward(self, logits, label):
        if in_mp_region(self.axis):
            axis = self.axis

            def local_ce(lg, lb):
                n = axis_size(axis)
                vocab_local = lg.shape[-1]
                start = lax.axis_index(axis) * vocab_local
                # stop_gradient on the INPUT: the max shift cancels in the CE
                # gradient, and lax.pmax has no differentiation rule, so pmax
                # must never see a tangent-carrying tracer
                m = lax.pmax(jnp.max(lax.stop_gradient(lg), axis=-1), axis)
                z = lg - m[..., None]
                sumexp = lax.psum(jnp.sum(jnp.exp(z), axis=-1), axis)
                lb_ = jnp.squeeze(lb, -1) if lb.ndim == lg.ndim else lb
                local = lb_ - start
                ok = (local >= 0) & (local < vocab_local)
                tgt = jnp.take_along_axis(
                    z, jnp.where(ok, local, 0)[..., None], axis=-1)[..., 0]
                tgt = lax.psum(jnp.where(ok, tgt, 0.0), axis)
                loss = jnp.log(sumexp) - tgt
                loss = jnp.where(lb_ == self.ignore_index, 0.0, loss)
                return loss[..., None]
            return forward_op("parallel_cross_entropy", local_ce,
                              [ensure_tensor(logits), ensure_tensor(label)])
        loss = F.cross_entropy(logits, label, reduction="none",
                               ignore_index=self.ignore_index)
        from .....ops import manipulation
        return manipulation.unsqueeze(loss, -1)  # [..., 1] (reference shape)


# ---------------------------------------------------------------------------
# r5: the legacy c_* compute ops behind the layers above (ref:
# c_embedding_op / c_softmax_with_cross_entropy_op). The communication-only
# c_* clones are compiled HLO collectives (SURVEY §2.5 design row); these
# two carry real compute, so they get functional forms: each performs the
# LOCAL shard's work + the collective the kernel fuses upstream.
# ---------------------------------------------------------------------------

def c_embedding(table, ids, start_index: int = 0, vocab_size: int = -1,
                group=None, name=None):
    """Vocab-shard embedding lookup: rows outside this shard's
    [start_index, start_index + rows) contribute zero; an all_reduce over
    the mp group (when initialized) merges the shards."""
    import jax.numpy as jnp
    from paddle_tpu.ops._helpers import ensure_tensor, forward_op
    tt = ensure_tensor(table)
    it = ensure_tensor(ids)

    def impl(tv, iv):
        local = iv - start_index
        ok = (local >= 0) & (local < tv.shape[0])
        safe = jnp.clip(local, 0, tv.shape[0] - 1)
        out = tv[safe] * ok[..., None]
        return out

    out = forward_op("c_embedding", impl, [tt, it])
    from paddle_tpu.distributed import collective as C
    if C.is_initialized() and C.get_world_size(group) > 1:
        out = C.all_reduce(out, group=group)
    return out


def c_softmax_with_cross_entropy(logits, label, group=None,
                                 ignore_index: int = -100, name=None):
    """Vocab-sharded softmax CE: the kernel the reference fuses for
    vocab-parallel heads — delegates to ParallelCrossEntropy's
    formulation (max/sum/logit gathers over the mp axis) when a mesh is
    active, plain CE otherwise."""
    from paddle_tpu.ops._helpers import ensure_tensor
    from paddle_tpu.distributed import collective as C
    if C.is_initialized() and C.get_world_size(group) > 1:
        ce = ParallelCrossEntropy()
        return ce(ensure_tensor(logits), ensure_tensor(label))
    from paddle_tpu.nn import functional as F
    return F.cross_entropy(logits, label, reduction="none",
                           ignore_index=ignore_index)


def _register_c_ops():
    from paddle_tpu.core.dispatch import OP_REGISTRY, register_op
    for _n, _f in (("c_embedding", c_embedding),
                   ("c_softmax_with_cross_entropy",
                    c_softmax_with_cross_entropy)):
        if _n not in OP_REGISTRY:
            register_op(_n, _f,
                        (_f.__doc__ or "").strip().split("\n")[0],
                        category="distributed", public=_f)


_register_c_ops()
