"""Pallas kernels vs pure-jax oracle (the reference OpTest numpy-oracle +
gradient-check pattern, SURVEY.md §4), run in interpret mode on the CPU mesh."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import (apply_rope, flash_attention,
                                flash_attention_with_lse, rms_norm,
                                rope_cos_sin)


def sdpa_ref(q, k, v, causal=False):
    d = q.shape[-1]
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if kh.shape[1] != qh.shape[1]:  # GQA: repeat kv heads
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


def rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        q = rand((2, 256, 4, 64), 0)
        k = rand((2, 256, 4, 64), 1)
        v = rand((2, 256, 4, 64), 2)
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        want = sdpa_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        q = rand((1, 128, 8, 64), 0)
        k = rand((1, 128, 2, 64), 1)
        v = rand((1, 128, 2, 64), 2)
        out = flash_attention(q, k, v, causal=True)
        want = sdpa_ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_vs_reference(self, causal):
        q = rand((1, 128, 2, 64), 3)
        k = rand((1, 128, 2, 64), 4)
        v = rand((1, 128, 2, 64), 5)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=causal) ** 2).sum()

        def loss_ref(q, k, v):
            return (sdpa_ref(q, k, v, causal) ** 2).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)

    def test_gqa_grads(self):
        q = rand((1, 128, 4, 64), 6)
        k = rand((1, 128, 2, 64), 7)
        v = rand((1, 128, 2, 64), 8)
        g1 = jax.grad(lambda *a: (flash_attention(*a, causal=True) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: (sdpa_ref(*a, causal=True) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)

    def test_lse(self):
        q = rand((1, 128, 1, 64), 9)
        k = rand((1, 128, 1, 64), 10)
        v = rand((1, 128, 1, 64), 11)
        _, lse = flash_attention_with_lse(q, k, v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
        want = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16(self):
        q = rand((1, 128, 2, 64), 0).astype(jnp.bfloat16)
        k = rand((1, 128, 2, 64), 1).astype(jnp.bfloat16)
        v = rand((1, 128, 2, 64), 2).astype(jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        want = sdpa_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want), rtol=2e-2, atol=2e-2)


class TestRMSNorm:
    def test_forward(self):
        x = rand((4, 32, 256), 0)
        w = rand((256,), 1) * 0.1 + 1.0
        out = rms_norm(x, w, 1e-6)
        want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_grads(self):
        x = rand((8, 128), 2)
        w = rand((128,), 3) * 0.1 + 1.0

        def ref(x, w):
            return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
                    * w)

        g1 = jax.grad(lambda x, w: (rms_norm(x, w, 1e-6) ** 2).sum(),
                      argnums=(0, 1))(x, w)
        g2 = jax.grad(lambda x, w: (ref(x, w) ** 2).sum(), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                                   rtol=1e-4, atol=1e-5)


class TestRoPE:
    def test_forward_and_inverse(self):
        x = rand((2, 16, 4, 64), 0)
        cos, sin = rope_cos_sin(16, 64)
        out = apply_rope(x, cos, sin)

        # reference rotate-half
        x1, x2 = x[..., :32], x[..., 32:]
        rot = jnp.concatenate([-x2, x1], -1)
        want = x * cos[None, :, None, :] + rot * sin[None, :, None, :]
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # rotation by -theta inverts
        back = apply_rope(out, cos, -sin)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_is_exact_adjoint(self):
        x = rand((1, 8, 2, 32), 1)
        cos, sin = rope_cos_sin(8, 32)
        g1 = jax.grad(lambda x: (apply_rope(x, cos, sin) ** 2).sum())(x)

        def ref(x):
            x1, x2 = x[..., :16], x[..., 16:]
            rot = jnp.concatenate([-x2, x1], -1)
            return x * cos[None, :, None, :] + rot * sin[None, :, None, :]

        g2 = jax.grad(lambda x: (ref(x) ** 2).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-5, atol=1e-5)


class TestVarlenFlashAttention:
    """Packed-sequence (segment-ids) flash attention vs a masked jnp oracle."""

    @staticmethod
    def _oracle(q, k, v, seg, causal):
        import jax
        import jax.numpy as jnp
        B, S, H, D = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(D)
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        if causal:
            mask = mask & jnp.tril(jnp.ones((S, S), bool))[None, None]
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # zero rows that see nothing (oracle convention: output 0)
        any_visible = mask.any(-1, keepdims=True)
        p = jnp.where(any_visible, p, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(0)
        B, S, H, D = 2, 32, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)),
                               jnp.float32) for _ in range(3))
        # two packed sequences per row: [0]*20 + [1]*12
        seg = jnp.asarray(np.repeat([[0, 1]], [20, 12], axis=1).repeat(B, 0))
        out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=8, block_k=8)
        ref = self._oracle(q, k, v, seg, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_no_cross_segment_leakage(self):
        """Changing segment B's values must not affect segment A's outputs."""
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(1)
        B, S, H, D = 1, 16, 2, 8
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        seg = jnp.asarray([[0] * 8 + [1] * 8])
        out1 = flash_attention(q, k, v, segment_ids=seg, block_q=8, block_k=8)
        k2 = k.at[:, 8:].set(99.0)
        v2 = v.at[:, 8:].set(-99.0)
        out2 = flash_attention(q, k2, v2, segment_ids=seg, block_q=8,
                               block_k=8)
        np.testing.assert_allclose(np.asarray(out1[:, :8]),
                                   np.asarray(out2[:, :8]), atol=1e-6)

    def test_gradients_vs_oracle(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(2)
        B, S, H, D = 1, 16, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)),
                               jnp.float32) for _ in range(3))
        seg = jnp.asarray([[0] * 10 + [1] * 6])

        g1 = jax.grad(lambda *a: flash_attention(
            *a, causal=True, segment_ids=seg, block_q=8,
            block_k=8).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: self._oracle(
            *a, seg, True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_non_seg_path_unchanged(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 16, 2, 8)),
                               jnp.float32) for _ in range(3))
        out_none = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        seg = jnp.zeros((1, 16), jnp.int32)  # single segment == no masking
        out_seg = flash_attention(q, k, v, causal=True, segment_ids=seg,
                                  block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(out_none), np.asarray(out_seg),
                                   atol=1e-5)


class TestQuantMatmul:
    """Weight-only int8 matmul kernel (ref: weight_only_linear)."""

    def test_matches_dequantized_reference(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.quant_matmul import (quantize_weights,
                                                     weight_only_matmul)
        rng = np.random.RandomState(0)
        w = (rng.randn(256, 512) * 0.05).astype(np.float32)
        x = rng.randn(4, 64, 256).astype(np.float32)
        wq, s = quantize_weights(w)
        assert wq.dtype == jnp.int8 and s.shape == (512,)
        out = np.asarray(weight_only_matmul(jnp.asarray(x), wq, s),
                         np.float32)
        ref = x.reshape(-1, 256) @ (np.asarray(wq, np.float32)
                                    * np.asarray(s)[None, :])
        np.testing.assert_allclose(out.reshape(-1, 512), ref,
                                   rtol=2e-2, atol=2e-2)  # bf16 MXU acc
        # quantization noise vs the ORIGINAL weights stays ~1%
        full = x.reshape(-1, 256) @ w
        rel = np.abs(out.reshape(-1, 512) - full).max() / np.abs(full).max()
        assert rel < 0.05, rel

    def test_unblockable_shape_falls_back(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.quant_matmul import (quantize_weights,
                                                     weight_only_matmul)
        rng = np.random.RandomState(1)
        w = (rng.randn(100, 36) * 0.1).astype(np.float32)  # not tileable
        x = rng.randn(5, 100).astype(np.float32)
        wq, s = quantize_weights(w)
        out = np.asarray(weight_only_matmul(jnp.asarray(x), wq, s),
                         np.float32)
        ref = x @ (np.asarray(wq, np.float32) * np.asarray(s)[None, :])
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


class TestPagedAttention:
    """Flash-decoding paged-attention kernel (ISSUE 10) vs the serving
    engine's XLA fallback oracle (block-table gather + ``_masked_sdpa``),
    interpret mode on CPU. The fuzz sweeps GQA group counts, block sizes,
    ragged sequence lengths pinned to block boundaries +-1, fp and int8
    pools, and NaN-poisoned free blocks — the whole matrix the engine can
    hand the kernel."""

    @staticmethod
    def _oracle(q, pool, tbl, sl):
        from paddle_tpu.models.generation import _kv_gather
        from paddle_tpu.models.llama import _masked_sdpa
        M = q.shape[0]
        N, Hk, bs, D = pool["k"].shape
        C = tbl.shape[1] * bs
        kk, vv = _kv_gather({n: a[None] for n, a in pool.items()}, 0, tbl,
                            M, C, Hk, D)     # a pool of one layer
        mask = (jnp.arange(C)[None, :] <= sl[:, None])[:, None, :]
        return _masked_sdpa(q[:, None], kk, vv, mask)[:, 0]

    @staticmethod
    def _quantize(x):
        from paddle_tpu.models.generation import _kv_quantize
        return _kv_quantize(x)

    def _case(self, rng, quant: bool, poison: bool):
        from paddle_tpu.kernels.paged_attention import paged_attention
        bs = int(rng.choice([4, 8, 16]))
        Hk = int(rng.choice([1, 2, 4]))
        G = int(rng.choice([1, 2, 4]))          # GQA group size (H = Hk*G)
        D = int(rng.choice([8, 16]))
        M = int(rng.integers(1, 5))
        W = int(rng.integers(2, 5))
        N = M * W + 3                            # slack blocks stay free
        q = jnp.asarray(rng.standard_normal((M, Hk * G, D)), jnp.float32)
        kf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        vf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        # ragged lengths pinned around block boundaries: the off-by-one
        # regime where a mask bug shows
        cap = W * bs - 1
        picks = [bs - 1, bs, bs + 1, int(rng.integers(0, cap + 1))]
        sl = jnp.asarray([min(cap, picks[int(rng.integers(0, 4))])
                          for _ in range(M)], jnp.int32)
        used = rng.choice(np.arange(1, N), size=(M, W), replace=False)
        tbl = np.zeros((M, W), np.int32)
        for m in range(M):
            nb = int(sl[m]) // bs + 1
            tbl[m, :nb] = used[m, :nb]           # tail entries stay null(0)
        tbl = jnp.asarray(tbl)
        if poison:                               # free blocks hold stale NaN
            free = sorted(set(range(1, N)) - set(tbl.reshape(-1).tolist()))
            kf = kf.at[jnp.asarray(free)].set(jnp.nan)
            vf = vf.at[jnp.asarray(free)].set(jnp.nan)
        if quant:
            kq, ks = self._quantize(jnp.nan_to_num(kf))
            vq, vs = self._quantize(jnp.nan_to_num(vf))
            if poison:                           # poison the QUANT layout
                free = sorted(set(range(1, N)) -
                              set(np.asarray(tbl).reshape(-1).tolist()))
                ks = ks.at[jnp.asarray(free)].set(jnp.nan)
                vs = vs.at[jnp.asarray(free)].set(jnp.nan)
            pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            out = paged_attention(q, kq, vq, tbl, sl, k_scale=ks,
                                  v_scale=vs)
        else:
            pool = {"k": kf, "v": vf}
            out = paged_attention(q, kf, vf, tbl, sl)
        want = self._oracle(q, pool, tbl, sl)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("trial", range(4))
    def test_randomized_parity_fuzz(self, trial):
        rng = np.random.default_rng(100 + trial)
        self._case(rng, quant=False, poison=False)
        self._case(rng, quant=True, poison=False)

    @pytest.mark.parametrize("trial", range(2))
    def test_poisoned_freed_blocks_stay_contained(self, trial):
        """Stale NaN in freed/unowned blocks (the PR 6 null-block
        poisoning regression, kernel edition): outputs must stay finite
        and bit-match the containment-hardened oracle on fp AND int8
        pools — in-kernel V zeroing at never-attendable positions is the
        same contract as ``_masked_sdpa``'s."""
        rng = np.random.default_rng(200 + trial)
        self._case(rng, quant=False, poison=True)
        self._case(rng, quant=True, poison=True)

    def test_masked_tail_positions_ignored(self):
        """KV garbage WITHIN an owned block beyond seq_len (a reused
        block's stale tail) must not leak into the output: filling the
        tail with NaN leaves the result unchanged."""
        from paddle_tpu.kernels.paged_attention import paged_attention
        rng = np.random.default_rng(7)
        M, H, Hk, D, bs, W, N = 2, 4, 2, 8, 4, 3, 8
        q = jnp.asarray(rng.standard_normal((M, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        tbl = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
        sl = jnp.asarray([5, 9], jnp.int32)
        base = paged_attention(q, k, v, tbl, sl)
        # poison every position past each row's seq_len in its own blocks
        k2, v2 = k, v
        for m, (blocks, s) in enumerate((([1, 2], 5), ([3, 4, 5], 9))):
            for i, b in enumerate(blocks):
                for off in range(bs):
                    if i * bs + off > s:
                        k2 = k2.at[b, :, off].set(jnp.nan)
                        v2 = v2.at[b, :, off].set(jnp.nan)
        out = paged_attention(q, k2, v2, tbl, sl)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))

    @staticmethod
    def _oracle_multi(q, pool, tbl, sl, dl, window=None):
        """Gather + _masked_sdpa with the verify window: query offset i of
        slot m attends j <= sl[m] + min(i, dl[m]), and under ``window``
        only j > sl[m] + min(i, dl[m]) - window."""
        from paddle_tpu.models.generation import _kv_gather
        from paddle_tpu.models.llama import _masked_sdpa
        M, Q = q.shape[:2]
        N, Hk, bs, D = pool["k"].shape
        C = tbl.shape[1] * bs
        kk, vv = _kv_gather({n: a[None] for n, a in pool.items()}, 0, tbl,
                            M, C, Hk, D)     # a pool of one layer
        qi = jnp.arange(Q)
        hi = sl[:, None] + jnp.minimum(qi[None, :], dl[:, None])  # [M, Q]
        j = jnp.arange(C)[None, None, :]
        mask = j <= hi[:, :, None]
        if window is not None:
            mask &= j > hi[:, :, None] - window
        return _masked_sdpa(q, kk, vv, mask)

    @staticmethod
    def _check_multi(out, want, dl, **tol):
        """The multi-query contract: rows ``q <= dl`` match the oracle,
        rows past the slot's draft are written as zeros."""
        out = np.asarray(out, np.float32)
        real = np.arange(out.shape[1])[None, :] <= np.asarray(dl)[:, None]
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[real], np.asarray(
            want, np.float32)[real], **tol)
        assert not out[~real].any()

    @pytest.mark.parametrize("trial", range(3))
    def test_multiquery_verify_fuzz(self, trial):
        """The speculative-verify entry point (ISSUE 11): q [M, Q, H, D]
        with per-slot draft lengths vs the gather oracle, across GQA
        groups, block sizes, ragged boundary lengths, fp and int8 pools —
        including dl=0 rows (which must behave exactly like the decode
        entry point) and windows crossing block boundaries."""
        from paddle_tpu.kernels.paged_attention import paged_attention
        rng = np.random.default_rng(300 + trial)
        bs = int(rng.choice([4, 8]))
        Hk = int(rng.choice([1, 2]))
        G = int(rng.choice([1, 2, 4]))
        D = int(rng.choice([8, 16]))
        M = int(rng.integers(1, 4))
        Q = int(rng.choice([2, 4, 5]))
        W = int(rng.integers(2, 5))
        N = M * W + 3
        quant = bool(trial % 2)
        q = jnp.asarray(rng.standard_normal((M, Q, Hk * G, D)), jnp.float32)
        kf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        vf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), jnp.float32)
        cap = W * bs - Q                       # room for the draft window
        sl = jnp.asarray([int(rng.integers(0, cap + 1)) for _ in range(M)],
                         jnp.int32)
        dl = jnp.asarray([int(rng.integers(0, Q)) for _ in range(M)],
                         jnp.int32)
        used = rng.choice(np.arange(1, N), size=(M, W), replace=False)
        tbl = np.zeros((M, W), np.int32)
        for m in range(M):
            nb = (int(sl[m]) + int(dl[m])) // bs + 1
            tbl[m, :nb] = used[m, :nb]
        tbl = jnp.asarray(tbl)
        if quant:
            kq, ks = self._quantize(kf)
            vq, vs = self._quantize(vf)
            pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            out = paged_attention(q, kq, vq, tbl, sl, draft_lens=dl,
                                  k_scale=ks, v_scale=vs)
        else:
            pool = {"k": kf, "v": vf}
            out = paged_attention(q, kf, vf, tbl, sl, draft_lens=dl)
        want = self._oracle_multi(q, pool, tbl, sl, dl)
        self._check_multi(out, want, dl, rtol=3e-5, atol=3e-5)
        # dl=0 rows of the verify tile must match the decode entry point
        # on the same pool (row 0 attends exactly j <= sl)
        if quant:
            single = paged_attention(q[:, 0], kq, vq, tbl, sl,
                                     k_scale=ks, v_scale=vs)
        else:
            single = paged_attention(q[:, 0], kf, vf, tbl, sl)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(single), rtol=3e-5,
                                   atol=3e-5)

    @pytest.mark.parametrize("pool_kind", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("G", [1, 4])
    @pytest.mark.parametrize("Q", [1, 64])
    def test_tiling_follows_real_tokens(self, Q, G, pool_kind):
        """ISSUE 25's tiling at its edges, one call a case. ``W`` (37) is
        not a multiple of ``P`` (16 at ``bs`` 4: three cells a slot, the
        last holding 5 pages); ``sl`` sits at 0, ``bs - 1``, ``P*bs - 1``,
        ``P*bs`` and the last position a window of ``Q`` fits. At ``Q`` 64
        the call mixes ``dl == 0`` slots (the short tile) with a full
        chunk, and two partial ones: under ``G`` 4 (256 query rows a kv
        head) those run 2, 2 and 1 sub-tiles of 128 rows, under ``G`` 1
        the whole 64-row tile. Every position past ``sl + dl`` — unowned
        blocks, the null block, the live cells' own tails — then takes
        NaN, which must change no bit; ``dl == 0`` slots equal the
        decode entry point."""
        import importlib
        pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
        bs, W, Hk, D = 4, 37, 2, 16
        dt = jnp.bfloat16 if pool_kind == "bf16" else jnp.float32
        P, R0, TQ = pa._tiling(bs, W, G, Q, dt)
        assert P == 16 and W % P and R0 <= TQ
        if Q > 1:
            assert TQ == (128 if G == 4 else 64)
        C = W * bs
        sl = np.array([0, bs - 1, P * bs - 1, P * bs, C - Q], np.int32)
        dl = (np.array([0, Q - 1, 40, 0, 20], np.int32) if Q > 1
              else np.zeros(5, np.int32))
        M, N = len(sl), len(sl) * W + 3
        rng = np.random.default_rng(25)
        q = jnp.asarray(rng.standard_normal((M, Q, Hk * G, D)), dt)
        kf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), dt)
        vf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), dt)
        tbl = rng.permutation(np.arange(1, M * W + 1)).reshape(M, W)
        need = (sl + dl) // bs + 1
        tbl = np.where(np.arange(W)[None, :] < need[:, None], tbl, 0)
        dead = np.ones((N, bs), bool)            # no query may attend
        for m in range(M):
            dead[tbl[m]] &= (np.arange(C) > sl[m] + dl[m]).reshape(W, bs)
        dead[0] = True
        dead = jnp.asarray(dead)
        tbl, sl, dl = (jnp.asarray(a, jnp.int32) for a in (tbl, sl, dl))

        def run(qq, poison):
            kw = {"draft_lens": dl} if qq.ndim == 4 else {}
            if pool_kind == "int8":
                kq, ks = self._quantize(kf)
                vq, vs = self._quantize(vf)
                if poison:                       # poison the QUANT layout
                    ks = jnp.where(dead[:, None, :], jnp.nan, ks)
                    vs = jnp.where(dead[:, None, :], jnp.nan, vs)
                pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
                kw.update(k_scale=ks, v_scale=vs)
            else:
                k, v = ((jnp.where(dead[:, None, :, None], jnp.nan, x)
                         for x in (kf, vf)) if poison else (kf, vf))
                pool = {"k": k, "v": v}
            return pool, pa.paged_attention(qq, pool["k"], pool["v"], tbl,
                                            sl, **kw)

        pool, out = run(q if Q > 1 else q[:, 0], poison=False)
        assert out.dtype == (jnp.float32 if pool_kind == "int8" else dt)
        wide = {k: v.astype(jnp.float32) if k in ("k", "v") and
                pool_kind != "int8" else v for k, v in pool.items()}
        want = self._oracle_multi(q.astype(jnp.float32), wide, tbl, sl, dl)
        # a bf16 output is rounded once, to 8 bits; the sums are fp32
        tol = (dict(rtol=1e-2, atol=1e-2) if pool_kind == "bf16"
               else dict(rtol=3e-5, atol=3e-5))
        self._check_multi(out if Q > 1 else out[:, None], want, dl, **tol)
        _, poisoned = run(q if Q > 1 else q[:, 0], poison=True)
        np.testing.assert_array_equal(np.asarray(poisoned, np.float32),
                                      np.asarray(out, np.float32))
        if Q > 1:                                # the short tile IS decode
            _, single = run(q[:, 0], poison=False)
            short = np.asarray(dl) == 0
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[short, 0],
                np.asarray(single, np.float32)[short], **tol)

    @pytest.mark.parametrize("pool_kind", ["fp32", "bf16"])
    @pytest.mark.parametrize("edge", ["before_last", "last", "first",
                                      "second"])
    @pytest.mark.parametrize("windowed", [False, True])
    @pytest.mark.parametrize("G", [6, 9])
    def test_tiling_follows_real_tokens_once_a_cell(self, G, windowed, edge,
                                                    pool_kind):
        """ISSUE 34's nesting at its edges, one call a case: chunk rows of
        ``Q`` 128 under query groups of 6 and 9 (6 and 9 sub-tiles of 128
        rows, each run against K and V rows stacked once a cell), the full
        form and the window-bounded one (a ring table; cells count from
        the window's first page, so they move with ``sl``). ``sl`` puts
        the first query's causal edge one BEFORE a cell's last position
        (one position to mask), on its LAST (the cell is interior: no mask
        runs), on its FIRST (one attendable position) and on its second:
        interior or edge, off by one, is the planted risk. A call holds a
        whole chunk, one whose ``(dl + 1) * G`` rows are no multiple of
        the sub-tile, a two-token row and a decode row, each at such an
        ``sl`` in a cell of its own. Then every
        position no query of the slot attends — unowned blocks, the null
        block, the tail past ``sl + dl``, pages that fell out of the ring,
        positions behind the window — takes NaN, which must change no
        bit."""
        import importlib
        pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
        Q, Hk, D = 128, 2, 16
        bs = 8 if G == 6 else 16                 # cells of 128 and of 256
        dt = jnp.bfloat16 if pool_kind == "bf16" else jnp.float32
        dl = np.array([Q - 1, 40, 1, 0], np.int32)
        M = len(dl)
        P, R0, TQ = pa._tiling(bs, 64, G, Q, dt)
        C = P * bs
        assert C == 16 * bs and TQ == 128 and R0 <= 16
        assert Q * G == G * TQ                   # G sub-tiles a whole chunk
        assert (41 * G) % TQ                     # a last sub-tile part full
        # (a window of 2C - 1 lets a deep sl sit at any of the four offsets)
        window = 2 * C - 1 if windowed else None

        def offset(s):                           # of sl in its cell
            lo = max(s - window + 1, 0) if windowed else 0
            return (s - lo // bs * bs) % C
        want_off = {"before_last": C - 2, "last": C - 1, "first": 0,
                    "second": 1}[edge]
        # one sl a slot, each in another cell, the first the deepest
        sl = np.array([next(s for s in range(k * C + C // 2, (k + 2) * C)
                            if offset(s) == want_off)
                       for k in (3, 2, 1, 0)], np.int32)
        pages = (sl + dl) // bs + 1              # a slot's pages, linear
        Wlin = int(pages.max())
        R = (window + Q) // bs + 2 if windowed else Wlin
        N = M * Wlin + 3
        rng = np.random.default_rng(34)
        q = jnp.asarray(rng.standard_normal((M, Q, Hk * G, D)), dt)
        kf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), dt)
        vf = jnp.asarray(rng.standard_normal((N, Hk, bs, D)), dt)
        lin = rng.permutation(np.arange(1, M * Wlin + 1)).reshape(M, Wlin)
        lin = np.where(np.arange(Wlin)[None, :] < pages[:, None], lin, 0)
        tbl = lin
        if windowed:                             # page p at entry p % R: a
            tbl = np.zeros((M, R), np.int64)     # later page takes its place
            for m in range(M):
                for pg in range(int(pages[m])):
                    tbl[m, pg % R] = lin[m, pg]
        dead = np.ones((N, bs), bool)            # no query may attend
        j = np.arange(Wlin * bs)
        for m in range(M):
            seen = j <= sl[m] + dl[m]
            if windowed:
                seen &= j > sl[m] - window
            dead[lin[m]] &= ~seen.reshape(Wlin, bs)
        dead[0] = True
        dead = jnp.asarray(dead)
        lin, tbl, sl, dl = (jnp.asarray(a, jnp.int32)
                            for a in (lin, tbl, sl, dl))

        def run(poison):
            k, v = ((jnp.where(dead[:, None, :, None], jnp.nan, x)
                     for x in (kf, vf)) if poison else (kf, vf))
            return pa.paged_attention(q, k, v, tbl, sl, draft_lens=dl,
                                      window=window)

        out = run(poison=False)
        wide = {"k": kf.astype(jnp.float32), "v": vf.astype(jnp.float32)}
        want = self._oracle_multi(q.astype(jnp.float32), wide, lin, sl, dl,
                                  window)
        tol = (dict(rtol=1e-2, atol=1e-2) if pool_kind == "bf16"
               else dict(rtol=3e-5, atol=3e-5))
        self._check_multi(out, want, dl, **tol)
        np.testing.assert_array_equal(
            np.asarray(run(poison=True), np.float32),
            np.asarray(out, np.float32))

    @pytest.mark.parametrize("pool_kind", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("Hk", [1, 4])
    @pytest.mark.parametrize("Q", [1, 8])
    def test_whole_pool_reads_its_layer(self, Q, Hk, pool_kind):
        """Handed EVERY layer's pool ``[L, N, Hk, bs, D]`` and
        ``layer`` (traced, as under a layer scan) the kernel returns, for
        each layer, what the one-layer call on ``pool[layer]`` returns,
        bit for bit: decode and multi-query form, fp32, bf16 and int8
        with its scale planes ``[L, N, Hk, bs]``. ``Hk`` 1 is a pool of
        one kv head under bf16 and int8: a page of one ``(bs, D)`` tile,
        copied whole out of the whole pool like any other."""
        from paddle_tpu.kernels.paged_attention import paged_attention
        L, M, G, D, bs, W = 3, 3, 2, 8, 4, 3
        N = M * W + 2
        dt = jnp.bfloat16 if pool_kind == "bf16" else jnp.float32
        rng = np.random.default_rng(30)
        q = jnp.asarray(rng.standard_normal((M, Q, Hk * G, D)), dt)
        kf = jnp.asarray(rng.standard_normal((L, N, Hk, bs, D)), dt)
        vf = jnp.asarray(rng.standard_normal((L, N, Hk, bs, D)), dt)
        tbl = jnp.asarray(rng.permutation(np.arange(1, M * W + 1))
                          .reshape(M, W), jnp.int32)
        sl = jnp.asarray([0, bs, W * bs - Q], jnp.int32)
        kw = {"draft_lens": jnp.asarray([0, Q - 1, Q // 2], jnp.int32)} \
            if Q > 1 else {}
        qq = q if Q > 1 else q[:, 0]
        pool = {"k": kf, "v": vf}
        if pool_kind == "int8":
            (kq, ks), (vq, vs) = self._quantize(kf), self._quantize(vf)
            pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}

        def call(p, **layer):
            return paged_attention(qq, p["k"], p["v"], tbl, sl,
                                   k_scale=p.get("k_scale"),
                                   v_scale=p.get("v_scale"), **kw, **layer)

        whole = jax.jit(lambda layer: call(pool, layer=layer))
        one = jax.jit(lambda layer: call({n: a[layer]
                                          for n, a in pool.items()}))
        outs = [np.asarray(whole(jnp.int32(i)), np.float32)
                for i in range(L)]
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(
                out, np.asarray(one(jnp.int32(i)), np.float32))
        assert not np.array_equal(outs[0], outs[1])   # the layers differ

    def test_pool_rank_and_layer_go_together(self):
        """A pool of every layer without ``layer``, or one layer's with
        it, raises: read silently, either would attend another layer's
        (or another block's) entries."""
        from paddle_tpu.kernels.paged_attention import paged_attention
        q = jnp.zeros((1, 2, 8), jnp.float32)
        k = jnp.zeros((2, 3, 1, 4, 8), jnp.float32)
        tbl, sl = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="layer"):
            paged_attention(q, k, k, tbl, sl)
        with pytest.raises(ValueError, match="layer"):
            paged_attention(q, k[0], k[0], tbl, sl, layer=0)

    def test_multiquery_requires_draft_lens(self):
        """Both halves of the entry-point contract: rank-4 q needs
        draft_lens, and rank-3 q REJECTS one (a silently-discarded
        draft operand would surface only as wrong attention)."""
        from paddle_tpu.kernels.paged_attention import paged_attention
        q = jnp.zeros((1, 2, 2, 8), jnp.float32)
        k = jnp.zeros((3, 4, 1, 8), jnp.float32)
        with pytest.raises(ValueError, match="draft_lens"):
            paged_attention(q, k, k, jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1,), jnp.int32))
        with pytest.raises(ValueError, match="single-token"):
            paged_attention(q[:, 0], k, k, jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1,), jnp.int32),
                            draft_lens=jnp.zeros((1,), jnp.int32))

    def test_use_pallas_knob_resolution(self):
        """The ONE kernel-dispatch gate (ISSUE 10 satellite): on/off/auto
        resolution shared by every kernel entry point."""
        from paddle_tpu.kernels import interpret, on_tpu, use_pallas
        assert use_pallas(True) is True
        assert use_pallas("on") is True
        assert use_pallas(False) is False
        assert use_pallas(None) is False
        assert use_pallas("off") is False
        assert use_pallas("") is False
        assert use_pallas("auto") == on_tpu()
        assert interpret() == (not on_tpu())
        with pytest.raises(ValueError, match="options"):
            use_pallas("sometimes")


class TestVarlenBlockSkip:
    """r3: segment-disjoint tiles are SKIPPED (splash-style sparsity).
    The skip predicate is range-based, so it must stay CORRECT for
    arbitrary (even unsorted) segment ids and block-unaligned boundaries."""

    def _run(self, seg_row, S=256, B=2, H=2, D=32):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
                   for kk in ks)
        seg = jnp.asarray(np.tile(seg_row, (B, 1)))
        out = flash_attention(q, k, v, causal=True, segment_ids=seg)

        # oracle: jnp masked softmax
        import math
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(D)
        m = jnp.tril(jnp.ones((S, S), bool))[None, None] & \
            (seg[:, None, :, None] == seg[:, None, None, :])
        s = jnp.where(m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(m.any(-1, keepdims=True), p, 0.0)
        ref = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_block_unaligned_segments(self):
        # boundaries at 100/190: never aligned with the 128 test blocks
        row = np.zeros(256, np.int32)
        row[100:190] = 1
        row[190:] = 2
        self._run(row)

    def test_unsorted_segment_ids_stay_correct(self):
        # interleaved pattern defeats the range skip (ranges always
        # overlap) — the kernel must fall back to masking, not mis-skip
        row = (np.arange(256) % 3).astype(np.int32)
        self._run(row)

    def test_many_tiny_segments(self):
        row = np.repeat(np.arange(32), 8).astype(np.int32)
        self._run(row)
