"""Regression tests for round-1 verdict/advice findings: causal-mask alignment
for Sq != Sk, PROD allreduce sign handling, scatter semantics, default-group
world span, fleet degree auto-infer, and per-axis rank queries."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.topology import (HybridCommunicateGroup,
                                             set_hybrid_communicate_group)
from paddle_tpu.kernels import flash_attention


@pytest.fixture
def reset_hcg():
    yield
    set_hybrid_communicate_group(None)


def sdpa_ref(q, k, v, causal=False):
    d = q.shape[-1]
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


class TestCausalBottomRightAlignment:
    """Chunked-prefill shape: Sq < Sk must match the tril(k=Sk-Sq) oracle."""

    @pytest.mark.parametrize("sq,sk", [(128, 256), (128, 384), (256, 256)])
    def test_forward(self, sq, sk):
        r = np.random.RandomState(7)
        q = jnp.asarray(r.randn(1, sq, 2, 64).astype(np.float32))
        k = jnp.asarray(r.randn(1, sk, 2, 64).astype(np.float32))
        v = jnp.asarray(r.randn(1, sk, 2, 64).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        want = sdpa_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_backward(self):
        r = np.random.RandomState(8)
        q = jnp.asarray(r.randn(1, 128, 2, 64).astype(np.float32))
        k = jnp.asarray(r.randn(1, 256, 2, 64).astype(np.float32))
        v = jnp.asarray(r.randn(1, 256, 2, 64).astype(np.float32))

        def f_flash(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()

        def f_ref(q, k, v):
            return sdpa_ref(q, k, v, causal=True).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestProdAllreduce:
    def test_signs_and_zeros(self, reset_hcg):
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=8))
        x = np.array([[2.0], [-3.0], [1.0], [-1.0], [0.5], [2.0], [1.0], [1.0]],
                     np.float32)
        t = paddle.to_tensor(x)
        dist.all_reduce(t, op=dist.ReduceOp.PROD)
        np.testing.assert_allclose(t.numpy(), np.full((8, 1), np.prod(x),
                                                      np.float32), rtol=1e-6)
        # zero anywhere -> exact 0, not -inf/NaN
        x0 = x.copy()
        x0[3] = 0.0
        t0 = paddle.to_tensor(x0)
        dist.all_reduce(t0, op=dist.ReduceOp.PROD)
        np.testing.assert_array_equal(t0.numpy(), np.zeros((8, 1), np.float32))


class TestScatter:
    def test_tensor_list(self, reset_hcg):
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=8))
        parts = [paddle.to_tensor(np.full((3,), float(r), np.float32))
                 for r in range(8)]
        out = dist.scatter(parts[0], parts)
        assert tuple(out.shape) == (8, 3)
        np.testing.assert_allclose(out.numpy()[5], np.full(3, 5.0))

    def test_split_src(self, reset_hcg):
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=8))
        full = paddle.to_tensor(np.arange(16, dtype=np.float32).reshape(16, 1))
        out = dist.scatter(full)
        assert tuple(out.shape) == (8, 2, 1)
        np.testing.assert_allclose(out.numpy()[3].ravel(), [6.0, 7.0])

    def test_bad_list_length(self, reset_hcg):
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=8))
        with pytest.raises(ValueError, match="ranks"):
            dist.scatter(paddle.to_tensor(np.ones(3, np.float32)),
                         [paddle.to_tensor(np.ones(3, np.float32))] * 3)


class TestDefaultGroupSpansWorld:
    def test_hybrid_mesh_all_reduce(self, reset_hcg):
        # dp=2 x mp=4: default group must reduce over all 8 devices
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=2, mp=4))
        t = paddle.to_tensor(np.ones((8, 2), np.float32))
        dist.all_reduce(t)
        np.testing.assert_allclose(t.numpy(), np.full((8, 2), 8.0))

    def test_world_size(self, reset_hcg):
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=2, mp=4))
        assert dist.get_world_size() == 8


class TestFleetDegreeNormalization:
    def test_dp_auto_infer_minus_one(self, reset_hcg):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": -1, "mp_degree": 4,
                                   "pp_degree": 1, "sharding_degree": 1,
                                   "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 4

    def test_rank_queries(self, reset_hcg):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        # single-controller process owns the whole axis -> canonical 0
        assert hcg.get_data_parallel_rank() == 0
        assert hcg.get_model_parallel_rank() == 0
        # trivial axes report 0 without device introspection
        assert hcg.get_stage_id() == 0

    def test_rank_inside_shard_region(self, reset_hcg):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        set_hybrid_communicate_group(HybridCommunicateGroup(dp=8))
        hcg = fleet.get_hybrid_communicate_group()

        def body(x):
            return x + hcg.get_data_parallel_rank()

        out = shard_map(body, mesh=hcg.mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))(jnp.zeros(8))
        np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


class TestPipelineGhostImport:
    def test_distributed_model_pp_raises_clearly(self, reset_hcg):
        import importlib
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        try:
            importlib.import_module("paddle_tpu.distributed.pipeline")
        except ImportError:
            # until the module lands, the pp path must raise NotImplementedError,
            # not ModuleNotFoundError from deep inside fleet
            with pytest.raises(NotImplementedError):
                fleet.distributed_model(object())
