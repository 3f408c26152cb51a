"""Tensor-parallel (mpu), sequence-parallel, and recompute tests.

Oracle (SURVEY §4): loss/output parity vs the serial layer with identical
weights — the reference's hybrid-parallel test pattern (test_dist_base.py),
run on the virtual 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.layers.mpu import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, get_rng_state_tracker, model_parallel_random_seed)
from paddle_tpu.distributed.fleet.layers.mpu import mp_ops
from paddle_tpu.distributed.fleet.utils import sequence_parallel_utils as spu
from paddle_tpu.distributed.fleet.recompute import recompute
from paddle_tpu.distributed.topology import set_hybrid_communicate_group


@pytest.fixture
def mp_mesh():
    st = fleet.DistributedStrategy()
    st.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=st)
    yield fleet.get_hybrid_communicate_group()
    set_hybrid_communicate_group(None)


def _clone_linear(src, in_f, out_f):
    dst = nn.Linear(in_f, out_f)
    dst.weight.set_value(src.weight.numpy())
    dst.bias.set_value(src.bias.numpy())
    return dst


class TestColumnRowParallel:
    def test_column_gather_fwd_bwd(self, mp_mesh):
        col = ColumnParallelLinear(16, 32, gather_output=True)
        ser = _clone_linear(col, 16, 32)
        x1 = paddle.to_tensor(np.random.randn(4, 16).astype("float32"),
                              stop_gradient=False)
        x2 = paddle.to_tensor(x1.numpy(), stop_gradient=False)
        y1, y2 = col(x1), ser(x2)
        np.testing.assert_allclose(y1.numpy(), y2.numpy(), atol=1e-5)
        y1.sum().backward()
        y2.sum().backward()
        np.testing.assert_allclose(col.weight.grad.numpy(),
                                   ser.weight.grad.numpy(), atol=1e-5)
        np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(), atol=1e-5)

    def test_column_row_pair(self, mp_mesh):
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        s1 = _clone_linear(col, 16, 32)
        s2 = _clone_linear(row, 32, 16)
        x = paddle.to_tensor(np.random.randn(4, 16).astype("float32"))
        o1 = row(F.relu(col(x)))
        o2 = s2(F.relu(s1(x)))
        np.testing.assert_allclose(o1.numpy(), o2.numpy(), atol=1e-5)

    def test_row_standalone(self, mp_mesh):
        row = RowParallelLinear(32, 16, input_is_parallel=False)
        ser = _clone_linear(row, 32, 16)
        x = paddle.to_tensor(np.random.randn(4, 32).astype("float32"))
        np.testing.assert_allclose(row(x).numpy(), ser(x).numpy(), atol=1e-5)

    def test_divisibility_check(self, mp_mesh):
        with pytest.raises(ValueError):
            ColumnParallelLinear(16, 30)
        with pytest.raises(ValueError):
            RowParallelLinear(30, 16)

    def test_mp_transformer_trains_identically(self, mp_mesh):
        """2-layer MLP-transformer block: serial vs mp=4, few SGD steps."""
        class Block(nn.Layer):
            def __init__(self, parallel):
                super().__init__()
                if parallel:
                    self.fc1 = ColumnParallelLinear(16, 64, gather_output=False)
                    self.fc2 = RowParallelLinear(64, 16, input_is_parallel=True)
                else:
                    self.fc1 = nn.Linear(16, 64)
                    self.fc2 = nn.Linear(64, 16)

            def forward(self, x):
                return self.fc2(F.gelu(self.fc1(x)))

        mp_model, ser_model = Block(True), Block(False)
        ser_model.fc1.weight.set_value(mp_model.fc1.weight.numpy())
        ser_model.fc1.bias.set_value(mp_model.fc1.bias.numpy())
        ser_model.fc2.weight.set_value(mp_model.fc2.weight.numpy())
        ser_model.fc2.bias.set_value(mp_model.fc2.bias.numpy())
        from paddle_tpu.optimizer import SGD
        opt1 = SGD(learning_rate=0.1, parameters=mp_model.parameters())
        opt2 = SGD(learning_rate=0.1, parameters=ser_model.parameters())
        xs = np.random.randn(3, 8, 16).astype("float32")
        losses = [[], []]
        for model, opt, rec in ((mp_model, opt1, losses[0]),
                                (ser_model, opt2, losses[1])):
            for i in range(3):
                x = paddle.to_tensor(xs[i])
                loss = (model(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
                rec.append(float(loss))
        np.testing.assert_allclose(losses[0], losses[1], atol=1e-5)


class TestVocabParallelEmbedding:
    def test_parity(self, mp_mesh):
        emb = VocabParallelEmbedding(64, 8)
        ser = nn.Embedding(64, 8)
        ser.weight.set_value(emb.weight.numpy())
        ids = paddle.to_tensor(np.random.randint(0, 64, (4, 7)))
        np.testing.assert_allclose(emb(ids).numpy(), ser(ids).numpy(), atol=1e-6)

    def test_vocab_divisibility(self, mp_mesh):
        with pytest.raises(ValueError):
            VocabParallelEmbedding(63, 8)

    def test_shard_map_masked_lookup(self, mp_mesh):
        """The Megatron masked-lookup path inside an explicit shard_map region."""
        emb = VocabParallelEmbedding(64, 8)
        full_w = emb.weight.numpy()
        ids = np.random.randint(0, 64, (4, 7))

        def body(w_local, ids_rep):
            from paddle_tpu.core.tensor import _wrap_value
            wt = _wrap_value(w_local)
            it = _wrap_value(ids_rep)
            emb2 = object.__new__(VocabParallelEmbedding)
            nn.Layer.__init__(emb2)
            emb2.axis = "mp"
            emb2.num_embeddings = 64
            emb2.embedding_dim = 8
            emb2.world_size = 4
            emb2._parameters["weight"] = wt
            return emb2(it)._raw

        f = shard_map(body, mesh=mp_mesh.mesh,
                      in_specs=(P("mp", None), P()), out_specs=P(), check_vma=False)
        out = f(jnp.asarray(full_w), jnp.asarray(ids))
        expected = full_w[ids]
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-6)


class TestParallelCrossEntropy:
    def test_parity_gspmd(self, mp_mesh):
        pce = ParallelCrossEntropy()
        logits = paddle.to_tensor(np.random.randn(6, 64).astype("float32"))
        lab = paddle.to_tensor(np.random.randint(0, 64, (6, 1)))
        l1 = pce(logits, lab)
        l2 = F.cross_entropy(logits, lab, reduction="none")
        assert list(l1.shape) == [6, 1]
        np.testing.assert_allclose(l1.numpy()[:, 0], l2.numpy(), atol=1e-5)

    def test_parity_shard_map(self, mp_mesh):
        logits = np.random.randn(6, 64).astype("float32")
        lab = np.random.randint(0, 64, (6, 1))

        def body(lg_local, lb):
            from paddle_tpu.core.tensor import _wrap_value
            pce = ParallelCrossEntropy()
            return pce(_wrap_value(lg_local), _wrap_value(lb))._raw

        f = shard_map(body, mesh=mp_mesh.mesh,
                      in_specs=(P(None, "mp"), P()), out_specs=P(), check_vma=False)
        out = f(jnp.asarray(logits), jnp.asarray(lab))
        expected = F.cross_entropy(paddle.to_tensor(logits),
                                   paddle.to_tensor(lab),
                                   reduction="none").numpy()
        np.testing.assert_allclose(np.asarray(out)[:, 0], expected, atol=1e-4)


class TestMpOpsShardMap:
    def test_split_concat_roundtrip_and_grads(self, mp_mesh):
        x = np.random.randn(4, 32).astype("float32")

        def f(v):
            def body(vl):
                local = mp_ops._split_last(vl, "mp")
                return mp_ops._concat_last(local, "mp")
            return shard_map(body, mesh=mp_mesh.mesh, in_specs=P(),
                             out_specs=P(), check_vma=False)(v).sum()

        g = jax.grad(f)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g), np.ones_like(x), atol=1e-6)

    def test_identity_psum_pairing(self, mp_mesh):
        """c_identity fw=x; bw=psum(g) over mp (4 ranks -> grad x4)."""
        x = np.random.randn(8).astype("float32")

        def f(v):
            def body(vl):
                return mp_ops._identity_psum_bwd(vl, "mp").sum()
            return shard_map(body, mesh=mp_mesh.mesh, in_specs=P(),
                             out_specs=P(), check_vma=False)(v)

        g = jax.grad(lambda v: f(v).sum())(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g), 4.0 * np.ones_like(x),
                                   atol=1e-6)


class TestSequenceParallel:
    def test_scatter_gather_gspmd(self, mp_mesh):
        x = paddle.to_tensor(np.random.randn(8, 4, 6).astype("float32"))
        s = spu.ScatterOp.apply(x, axis=0)
        assert list(s.shape) == [8, 4, 6]  # full logical value, seq-sharded
        g = spu.GatherOp.apply(s, axis=0)
        np.testing.assert_allclose(g.numpy(), x.numpy(), atol=1e-6)

    def test_allgather_reducescatter_shard_map(self, mp_mesh):
        x = np.random.randn(8, 4).astype("float32")

        def f(v):
            def body(vl):
                up = spu._allgather_rs(vl, "mp", 0)     # [8,4] full
                return spu._rs_ag(up, "mp", 0)           # back to local [2,4]*psum
            return shard_map(body, mesh=mp_mesh.mesh,
                             in_specs=P("mp", None),
                             out_specs=P("mp", None), check_vma=False)(v)

        out = f(jnp.asarray(x))
        # all_gather then reduce_scatter over 4 ranks multiplies by the psum
        # of 4 identical copies
        np.testing.assert_allclose(np.asarray(out), 4.0 * x, atol=1e-5)

    def test_sequence_parallel_linears_parity(self, mp_mesh):
        col = spu.ColumnSequenceParallelLinear(16, 32, gather_output=False,
                                               seq_axis=0)
        row = spu.RowSequenceParallelLinear(32, 16, input_is_parallel=True,
                                            seq_axis=0)
        s1 = _clone_linear(col, 16, 32)
        s2 = _clone_linear(row, 32, 16)
        x = paddle.to_tensor(np.random.randn(8, 4, 16).astype("float32"))
        o1 = row(F.relu(col(spu.ScatterOp.apply(x, axis=0))))
        o1 = spu.GatherOp.apply(o1, axis=0)
        o2 = s2(F.relu(s1(x)))
        np.testing.assert_allclose(o1.numpy(), o2.numpy(), atol=1e-5)

    def test_mark_parameter(self, mp_mesh):
        p = paddle.to_tensor(np.zeros(3, np.float32))
        spu.mark_as_sequence_parallel_parameter(p)
        assert spu.is_sequence_parallel_parameter(p)


class TestRNGTracker:
    def test_tracker_streams(self, mp_mesh):
        model_parallel_random_seed(1234)
        tr = get_rng_state_tracker()
        k1 = tr.next_key()  # global stream
        with tr.rng_state():
            k2 = tr.next_key()
        k3 = tr.next_key()
        assert not np.array_equal(jax.random.key_data(k2),
                                  jax.random.key_data(k1))
        assert not np.array_equal(jax.random.key_data(k3),
                                  jax.random.key_data(k1))

    def test_duplicate_seed_rejected(self, mp_mesh):
        tr = get_rng_state_tracker()
        tr.reset()
        tr.add("a", 7)
        with pytest.raises(ValueError):
            tr.add("b", 7)
        with pytest.raises(ValueError):
            tr.add("a", 8)


class TestRecompute:
    def _model(self):
        m = nn.Sequential(nn.Linear(8, 32), nn.GELU(), nn.Linear(32, 8))
        return m

    def test_forward_backward_parity(self):
        m = self._model()
        x1 = paddle.to_tensor(np.random.randn(4, 8).astype("float32"),
                              stop_gradient=False)
        x2 = paddle.to_tensor(x1.numpy(), stop_gradient=False)
        y1 = recompute(m, x1)
        y2 = m(x2)
        np.testing.assert_allclose(y1.numpy(), y2.numpy(), atol=1e-6)
        y1.sum().backward()
        g_rc = [p.grad.numpy().copy() for p in m.parameters()]
        for p in m.parameters():
            p.clear_grad()
        y2.sum().backward()
        g_ref = [p.grad.numpy() for p in m.parameters()]
        for a, b in zip(g_rc, g_ref):
            np.testing.assert_allclose(a, b, atol=1e-5)
        np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(), atol=1e-5)

    def test_no_grad_passthrough(self):
        m = self._model()
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        with paddle.no_grad():
            y = recompute(m, x)
        assert y.shape == [4, 8]

    def test_dropout_consistent_forward_backward(self):
        """RNG preservation: grads must correspond to the same mask the forward
        used — check grad of x through dropout(recompute) equals mask/keep_prob."""
        drop = nn.Dropout(0.5)
        drop.train()
        x = paddle.to_tensor(np.ones((64,), np.float32), stop_gradient=False)
        y = recompute(lambda v: drop(v) * 2.0, x)
        y.sum().backward()
        # y = mask*x/0.5*2 -> dy/dx = mask*4; consistency: grad nonzero exactly
        # where y nonzero
        np.testing.assert_allclose((np.asarray(y.numpy()) != 0),
                                   (x.grad.numpy() != 0))

    def test_recompute_sequential(self):
        from paddle_tpu.distributed.fleet.recompute import recompute_sequential
        m = self._model()
        x1 = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        y1 = recompute_sequential({"segments": 2}, list(m), x1)
        y2 = m(x1)
        np.testing.assert_allclose(y1.numpy(), y2.numpy(), atol=1e-6)

    def test_mutating_function_falls_back(self):
        state = paddle.to_tensor(np.zeros(1, np.float32))

        def fn(v):
            state.set_value(state.numpy() + 1)
            return v * 2

        x = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        with pytest.warns(RuntimeWarning):
            y = recompute(fn, x)
        np.testing.assert_allclose(y.numpy(), 2 * np.ones(3), atol=1e-6)


class TestReviewFixes:
    """Round-2 inline-review regressions."""

    def test_fleet_recompute_callable_after_utils_import(self):
        import paddle_tpu.distributed.fleet.utils  # noqa: F401 triggers submodule import
        from paddle_tpu.distributed import fleet as fl
        from paddle_tpu.distributed.fleet.utils import recompute as utils_rc
        assert callable(utils_rc)
        # fleet.recompute is the package (reference layout); its .recompute is the fn
        assert callable(fl.recompute.recompute)

    def test_normally_constructed_layers_in_shard_map(self, mp_mesh):
        """Layers built normally (full weights, closed over) must slice their
        local shard inside a shard_map region — output parity vs serial."""
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        s1 = _clone_linear(col, 16, 32)
        s2 = _clone_linear(row, 32, 16)
        x = np.random.randn(4, 16).astype("float32")

        def body(xv):
            from paddle_tpu.core.tensor import _wrap_value
            h = col(_wrap_value(xv))
            return row(F.relu(h))._raw

        f = shard_map(body, mesh=mp_mesh.mesh, in_specs=P(),
                      out_specs=P(), check_vma=False)
        out = f(jnp.asarray(x))
        ref = s2(F.relu(s1(paddle.to_tensor(x)))).numpy()
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    def test_vocab_embedding_closure_in_shard_map(self, mp_mesh):
        emb = VocabParallelEmbedding(64, 8)
        ser = nn.Embedding(64, 8)
        ser.weight.set_value(emb.weight.numpy())
        ids = np.random.randint(0, 64, (4, 7))

        def body(iv):
            from paddle_tpu.core.tensor import _wrap_value
            return emb(_wrap_value(iv))._raw

        f = shard_map(body, mesh=mp_mesh.mesh, in_specs=P(),
                      out_specs=P(), check_vma=False)
        out = f(jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(out),
                                   ser(paddle.to_tensor(ids)).numpy(),
                                   atol=1e-6)

    def test_parallel_ce_ignore_index_shard_map(self, mp_mesh):
        logits = np.random.randn(6, 64).astype("float32")
        lab = np.random.randint(0, 64, (6, 1))
        lab[2, 0] = -100

        def body(lg_local, lb):
            from paddle_tpu.core.tensor import _wrap_value
            pce = ParallelCrossEntropy()
            return pce(_wrap_value(lg_local), _wrap_value(lb))._raw

        f = shard_map(body, mesh=mp_mesh.mesh,
                      in_specs=(P(None, "mp"), P()), out_specs=P(),
                      check_vma=False)
        out = np.asarray(f(jnp.asarray(logits), jnp.asarray(lab)))
        assert out[2, 0] == 0.0

    def test_recompute_state_cache_hit(self):
        from paddle_tpu.distributed.fleet.recompute import recompute as rc
        from paddle_tpu.distributed.fleet.recompute.recompute import (
            _STATE_CACHE, _cache_entry)
        m = nn.Sequential(nn.Linear(4, 8), nn.GELU(), nn.Linear(8, 4))
        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        rc(m, x)
        key, sub = _cache_entry(m)
        assert key in _STATE_CACHE and sub in _STATE_CACHE[key]
        y2 = rc(m, x)  # cache-hit path
        np.testing.assert_allclose(y2.numpy(), m(x).numpy(), atol=1e-6)

    def test_recompute_raw_output_leaf(self):
        from paddle_tpu.distributed.fleet.recompute import recompute as rc
        lin = nn.Linear(4, 4)

        def fn(v):
            y = lin(v)
            return y, y._value * 2  # second leaf is a raw jax array

        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        y, raw = rc(fn, x)
        assert isinstance(y, paddle.Tensor)
        assert not isinstance(raw, paddle.Tensor)
        np.testing.assert_allclose(np.asarray(raw), 2 * y.numpy(), atol=1e-6)

    def test_recompute_sequential_rejects_multi_args(self):
        from paddle_tpu.distributed.fleet.recompute import recompute_sequential
        m = nn.Sequential(nn.Linear(4, 4))
        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        with pytest.raises(ValueError):
            recompute_sequential({"segments": 1}, list(m), x, x)

    def test_recompute_sequential_segment_count(self):
        from paddle_tpu.distributed.fleet.recompute.recompute import recompute_sequential
        calls = []

        class Probe(nn.Layer):
            def forward(self, x):
                return x + 1

        layers = [Probe() for _ in range(8)]
        # segments=3 over 8 layers -> ceil(8/3)=3 per chunk -> 3 chunks
        import importlib
        rmod = importlib.import_module(
            "paddle_tpu.distributed.fleet.recompute.recompute")
        n_chunks = []
        real_rc = rmod.recompute
        try:
            rmod.recompute = lambda f, x, **k: (n_chunks.append(1), real_rc(f, x, **k))[1]
            x = paddle.to_tensor(np.zeros((2, 2), np.float32))
            y = recompute_sequential({"segments": 3}, layers, x)
        finally:
            rmod.recompute = real_rc
        assert len(n_chunks) == 3
        np.testing.assert_allclose(y.numpy(), 8 * np.ones((2, 2)), atol=1e-6)


class TestRound2ReviewFixes:
    def test_seq_parallel_column_grads_not_scaled(self, mp_mesh):
        """shard_map path: AllGatherOp's reduce-scatter backward must REPLACE
        c_identity's psum, not stack on it (was: input grads x mp_degree)."""
        col = spu.ColumnSequenceParallelLinear(16, 32, gather_output=False,
                                               seq_axis=0)
        ser = _clone_linear(col, 16, 32)
        x = np.random.randn(8, 4, 16).astype("float32")

        def f(v):
            def body(vl):
                from paddle_tpu.core.tensor import _wrap_value
                t = _wrap_value(vl)  # local seq shard [2,4,16]
                y = col(t)
                return y._raw
            out = shard_map(body, mesh=mp_mesh.mesh,
                            in_specs=P("mp", None, None),
                            out_specs=P("mp", None, None),
                            check_vma=False)(v)
            return (out ** 2).sum()

        g = jax.grad(f)(jnp.asarray(x))

        def f_ser(v):
            import paddle_tpu.nn.functional as Fn
            y = Fn.linear(paddle.to_tensor(v), ser.weight, ser.bias)
            return (y._raw.astype(jnp.float32) ** 2).sum()

        g_ser = jax.grad(lambda v: f_ser(v))(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ser),
                                   atol=1e-4, rtol=1e-4)

    def test_parallel_ce_trainable_logits_shard_map(self, mp_mesh):
        """pmax path must be differentiable (stop_gradient'ed max shift)."""
        logits = np.random.randn(6, 64).astype("float32")
        lab = np.random.randint(0, 64, (6, 1))

        def f(lg):
            def body(lg_local, lb):
                from paddle_tpu.core.tensor import _wrap_value
                pce = ParallelCrossEntropy()
                t = _wrap_value(lg_local, stop_gradient=False)
                return pce(t, _wrap_value(lb))._raw
            out = shard_map(body, mesh=mp_mesh.mesh,
                            in_specs=(P(None, "mp"), P()), out_specs=P(),
                            check_vma=False)(lg, jnp.asarray(lab))
            return out.sum()

        g = jax.grad(f)(jnp.asarray(logits))
        # oracle: d(sum CE)/dlogits = softmax - onehot
        p = jax.nn.softmax(jnp.asarray(logits), axis=-1)
        onehot = jax.nn.one_hot(jnp.asarray(lab)[:, 0], 64)
        np.testing.assert_allclose(np.asarray(g), np.asarray(p - onehot),
                                   atol=1e-4)

    def test_recompute_two_methods_same_object(self, mp_mesh):
        """State cache must key (obj, method) — second method of the same
        object must not reuse the first method's parameter list."""
        class TwoHeads(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(8, 8)
                self.fc2 = nn.Linear(8, 8)

            def head1(self, x):
                return self.fc1(x)

            def head2(self, x):
                return self.fc2(x)

        m = TwoHeads()
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        (recompute(m.head1, x) ** 2).mean().backward()
        assert m.fc1.weight.grad is not None
        (recompute(m.head2, x) ** 2).mean().backward()
        assert m.fc2.weight.grad is not None
        assert float(np.abs(m.fc2.weight.grad.numpy()).sum()) > 0
