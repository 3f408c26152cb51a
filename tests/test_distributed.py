"""Distributed core on the virtual 8-device CPU mesh (the reference's
Gloo-on-localhost pattern, SURVEY.md §4): collectives, shard_tensor/GSPMD layouts,
fleet topology, DataParallel + ZeRO loss-parity-vs-serial oracles."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet


@pytest.fixture(autouse=True)
def _reset_topology():
    yield
    dist.set_hybrid_communicate_group(None)


def t(a, sg=True):
    return paddle.to_tensor(a, stop_gradient=sg)


class TestCollectives:
    def setup_method(self, m):
        fleet.init(is_collective=True)  # dp=8 default

    def test_all_reduce_sum(self):
        x = t(np.arange(8, dtype=np.float32).reshape(8, 1))
        dist.all_reduce(x)
        np.testing.assert_allclose(x.numpy(), np.full((8, 1), 28.0))

    def test_all_reduce_max(self):
        x = t(np.arange(8, dtype=np.float32).reshape(8, 1))
        dist.all_reduce(x, op=dist.ReduceOp.MAX)
        np.testing.assert_allclose(x.numpy(), np.full((8, 1), 7.0))

    def test_all_gather(self):
        x = t(np.arange(16, dtype=np.float32).reshape(8, 2))
        out = dist.all_gather(x)
        assert out.shape == [8, 16]
        np.testing.assert_allclose(out.numpy()[0], np.arange(16, dtype=np.float32))
        np.testing.assert_allclose(out.numpy()[5], np.arange(16, dtype=np.float32))

    def test_reduce_scatter(self):
        x = t(np.ones((8, 8), np.float32))
        out = dist.reduce_scatter(x)
        assert out.shape == [8, 1]
        np.testing.assert_allclose(out.numpy(), np.full((8, 1), 8.0))

    def test_alltoall(self):
        # rank r sends row block c to rank c: out[r][c] = in[c][r]
        x = t(np.arange(64, dtype=np.float32).reshape(8, 8))
        out = dist.alltoall(x)
        np.testing.assert_allclose(out.numpy(),
                                   np.arange(64, dtype=np.float32)
                                   .reshape(8, 8).T)

    def test_broadcast(self):
        x = t(np.arange(8, dtype=np.float32).reshape(8, 1))
        dist.broadcast(x, src=3)
        np.testing.assert_allclose(x.numpy(), np.full((8, 1), 3.0))

    def test_world_size(self):
        assert dist.get_world_size() == 8
        assert dist.get_rank() == 0


class TestShardTensor:
    def test_shard_and_layout(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["x", "y"])
        w = t(np.random.rand(8, 6).astype(np.float32))
        sw = dist.shard_tensor(w, mesh, [dist.Shard(0), dist.Replicate()])
        shard_shapes = {tuple(s.data.shape) for s in sw._value.addressable_shards}
        assert shard_shapes == {(2, 6)}
        np.testing.assert_allclose(np.asarray(sw._value), w.numpy())

    def test_reshard(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["x", "y"])
        w = t(np.random.rand(8, 8).astype(np.float32))
        sw = dist.shard_tensor(w, mesh, [dist.Shard(0), dist.Shard(1)])
        rw = dist.reshard(sw, mesh, [dist.Replicate(), dist.Shard(0)])
        shard_shapes = {tuple(s.data.shape) for s in rw._value.addressable_shards}
        assert shard_shapes == {(4, 8)}
        np.testing.assert_allclose(np.asarray(rw._value), w.numpy())

    def test_computation_on_dist_tensors(self):
        mesh = dist.ProcessMesh(np.arange(8), ["x"])
        a = dist.shard_tensor(t(np.random.rand(16, 4).astype(np.float32)),
                              mesh, [dist.Shard(0)])
        b = dist.shard_tensor(t(np.random.rand(4, 3).astype(np.float32)),
                              mesh, [dist.Replicate()])
        out = paddle.matmul(a, b)  # GSPMD propagates the row sharding
        assert out.shape == [16, 3]
        np.testing.assert_allclose(
            np.asarray(out._value),
            np.asarray(a._value) @ np.asarray(b._value), rtol=1e-5)


class TestFleetTopology:
    def test_hybrid_mesh(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                                   "sharding_degree": 2, "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 2
        assert hcg.mesh.shape["dp"] == 2 and hcg.mesh.shape["mp"] == 2

    def test_wrong_degrees_raise(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 3, "mp_degree": 1, "pp_degree": 1,
                                   "sharding_degree": 1, "sep_degree": 1}
        with pytest.raises(ValueError):
            fleet.init(strategy=strategy)


def _train(model_fn, steps=6, wrap=None, shard_level=None, lr=0.1, batch=16):
    paddle.seed(123)
    rng = np.random.RandomState(5)
    X = rng.rand(batch, 8).astype(np.float32)
    Y = rng.rand(batch, 1).astype(np.float32)
    model = model_fn()
    opt = paddle.optimizer.Adam(learning_rate=lr, parameters=model.parameters())
    if shard_level:
        model, opt, _ = dist.group_sharded_parallel(model, opt, shard_level)
    if wrap:
        model = wrap(model)
    losses = []
    for _ in range(steps):
        loss = nn.functional.mse_loss(model(t(X)), t(Y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


def _mlp():
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))


class TestDataParallelParity:
    def test_dp_loss_matches_serial(self):
        fleet.init(is_collective=True)  # dp=8
        serial = _train(_mlp)
        dp = _train(_mlp, wrap=dist.DataParallel)
        np.testing.assert_allclose(serial, dp, rtol=2e-4, atol=1e-6)
        assert dp[-1] < dp[0]


class TestGroupSharded:
    def test_stage1_parity_and_layout(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                                   "sharding_degree": 8, "sep_degree": 1}
        fleet.init(strategy=strategy)
        serial = _train(_mlp)
        sharded = _train(_mlp, shard_level="os")
        np.testing.assert_allclose(serial, sharded, rtol=2e-4, atol=1e-6)

    def test_stage3_parity(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                                   "sharding_degree": 8, "sep_degree": 1}
        fleet.init(strategy=strategy)
        serial = _train(_mlp)
        sharded = _train(_mlp, shard_level="p_g_os")
        np.testing.assert_allclose(serial, sharded, rtol=2e-4, atol=1e-6)

    def test_stage1_states_are_sharded(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                                   "sharding_degree": 8, "sep_degree": 1}
        fleet.init(strategy=strategy)
        paddle.seed(0)
        model = nn.Linear(16, 16)
        opt = paddle.optimizer.Adam(parameters=model.parameters())
        dist.group_sharded_parallel(model, opt, "os")
        x = t(np.random.rand(4, 16).astype(np.float32))
        nn.functional.mse_loss(model(x), t(np.zeros((4, 16), np.float32))).backward()
        opt.step()
        m = opt._accumulators["moment1"][model.weight.name]
        shard_shapes = {tuple(s.data.shape)
                        for s in m._raw.addressable_shards}
        assert shard_shapes == {(2, 16)}, shard_shapes


class TestInGraphCollectives:
    def test_psum_inside_shard_map(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        fleet.init()
        hcg = fleet.get_hybrid_communicate_group()

        def body(x):
            y = dist.all_reduce(t(x), group="dp")
            return y._value

        f = shard_map(body, mesh=hcg.mesh, in_specs=(P("dp"),),
                      out_specs=P("dp"))
        x = jnp.arange(8.0).reshape(8, 1)
        out = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))
