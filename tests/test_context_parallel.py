"""Context parallelism tests: Ulysses + ring flash attention on the 8-device
CPU mesh, sep=4. Oracle: single-device attention (SURVEY §4 parity pattern)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.context_parallel import (ring_flash_attention,
                                                     sep_parallel_attention,
                                                     ulysses_attention,
                                                     _sdpa)
from paddle_tpu.distributed.topology import set_hybrid_communicate_group


@pytest.fixture
def sep_mesh():
    st = fleet.DistributedStrategy()
    st.hybrid_configs = {"dp_degree": 2, "sep_degree": 4, "mp_degree": 1}
    fleet.init(is_collective=True, strategy=st)
    yield fleet.get_hybrid_communicate_group()
    set_hybrid_communicate_group(None)


def _qkv(B=2, S=32, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.standard_normal((B, S, H, D)).astype("float32")
    return mk(), mk(), mk()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_parity_vs_serial(self, sep_mesh, causal):
        q, k, v = _qkv()
        ref, _ = _sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal)
        out = sep_parallel_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                     paddle.to_tensor(v), causal=causal,
                                     impl="ring", use_kernels=False)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_parity_with_flash_kernel(self, sep_mesh, causal):
        # Pallas kernel path (interpret mode on CPU) through the ring
        q, k, v = _qkv(B=1, S=32, H=2, D=8)
        ref, _ = _sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal)
        out = sep_parallel_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                     paddle.to_tensor(v), causal=causal,
                                     impl="ring", use_kernels=True)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_grads_match_serial(self, sep_mesh):
        q, k, v = _qkv(S=16)
        hcg = sep_mesh

        def ring_loss(qv, kv, vv):
            from jax import shard_map
            f = shard_map.__wrapped__ if hasattr(shard_map, "__wrapped__") \
                else shard_map
            sm = f(lambda a, b, c: ring_flash_attention(
                a, b, c, "sep", True, False),
                mesh=hcg.mesh,
                in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
                out_specs=P(None, "sep"), check_vma=False)
            return (sm(qv, kv, vv).astype(jnp.float32) ** 2).sum()

        def ref_loss(qv, kv, vv):
            return (_sdpa(qv, kv, vv, True)[0].astype(jnp.float32) ** 2).sum()

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_parity_vs_serial(self, sep_mesh, causal):
        q, k, v = _qkv()  # H=4 divisible by sep=4
        ref, _ = _sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal)
        out = sep_parallel_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                     paddle.to_tensor(v), causal=causal,
                                     impl="ulysses", use_kernels=False)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_head_divisibility_check(self, sep_mesh):
        from jax import shard_map
        q, k, v = _qkv(H=2)  # 2 heads, sep=4 -> error
        sm = shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, "sep", False, False),
            mesh=sep_mesh.mesh,
            in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
            out_specs=P(None, "sep"), check_vma=False)
        with pytest.raises(ValueError, match="divisible"):
            sm(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    def test_backward_through_tensor_wrapper(self, sep_mesh):
        q, k, v = _qkv()
        qt = paddle.to_tensor(q, stop_gradient=False)
        kt = paddle.to_tensor(k, stop_gradient=False)
        vt = paddle.to_tensor(v, stop_gradient=False)
        out = sep_parallel_attention(qt, kt, vt, causal=True, impl="ulysses",
                                     use_kernels=False)
        (out ** 2).sum().backward()
        for t in (qt, kt, vt):
            assert t.grad is not None
            assert np.isfinite(t.grad.numpy()).all()


class TestLongSeqBenchPoint:
    def test_ring_long_sequence_smoke(self, sep_mesh):
        """S=128 over 4 ranks — each rank only ever sees S/4 of K/V."""
        q, k, v = _qkv(B=1, S=128, H=4, D=8)
        out = sep_parallel_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                     paddle.to_tensor(v), causal=True,
                                     impl="ring", use_kernels=False)
        ref, _ = _sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), True)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=3e-5,
                                   rtol=3e-5)


class TestLlamaWithCP:
    def test_llama_ring_cp_matches_serial(self, sep_mesh):
        """Flagship model forward with sep ring attention == serial forward."""
        from paddle_tpu.models import llama
        import dataclasses
        cfg = llama.LlamaConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, use_kernels=False)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.arange(2 * 32).reshape(2, 32) % cfg.vocab_size
        ref = llama.forward(params, ids, cfg)
        cfg_cp = dataclasses.replace(cfg, sep_axis="sep", cp_impl="ring")
        got = llama.forward(params, ids, cfg_cp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_llama_ulysses_cp_matches_serial(self, sep_mesh):
        from paddle_tpu.models import llama
        import dataclasses
        cfg = llama.LlamaConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, use_kernels=False)  # GQA expanded inside
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        ids = jnp.arange(32).reshape(1, 32) % cfg.vocab_size
        ref = llama.forward(params, ids, cfg)
        cfg_cp = dataclasses.replace(cfg, sep_axis="sep", cp_impl="ulysses")
        got = llama.forward(params, ids, cfg_cp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_llama_ring_cp_train_step(self, sep_mesh):
        """Sharded train step under ring CP produces finite decreasing loss."""
        from paddle_tpu.models import llama
        import dataclasses
        from jax.sharding import NamedSharding
        cfg = llama.LlamaConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, use_kernels=False,
            sep_axis="sep", cp_impl="ring")
        params = llama.init_params(cfg, jax.random.PRNGKey(2))
        init_opt, step = llama.make_train_step(cfg, lr=1e-2)
        opt = init_opt(params)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 96, (2, 32)), jnp.int32)
        bs = NamedSharding(sep_mesh.mesh, llama.batch_spec(("dp",), "sep"))
        ids = jax.device_put(ids, bs)
        jstep = jax.jit(step)
        losses = []
        for _ in range(3):
            params, opt, loss = jstep(params, opt, ids, ids)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
