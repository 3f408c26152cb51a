"""Benchmark harness (BASELINE.md protocol).

Default run: EVERY bench point, one JSON line each on stdout (machine-
readable for the driver), the headline LAST:

    {"metric": "llama_train_mfu", "value": <pct>, "unit": "%", "vs_baseline": r}

The headline is the HONEST LLaMA-ratio config (I=5504, L=12 — LLaMA-7B
shape ratios at 738M scale); ``vs_baseline`` = measured MFU / the 50%
north-star from BASELINE.json. Secondary rows (wide-FFN variant, flash
attention vs XLA SDPA, ResNet-50, BERT-base, SDXL attention) carry
``vs_baseline`` relative to their round-2 recorded values so the driver can
track regressions. Detail (tokens/sec, step time, config, hardware) goes to
stderr and is copied into BASELINE.md rows.

Flags restrict the run to single sections (--llama, --wide, --attn,
--resnet, --bert, --sdxl); default = all, each section failure-isolated.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

import numpy as np


# bf16 peak TFLOP/s per chip by device kind (public spec sheets)
_PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v4": 275.0,
    "TPU v6 lite": 918.0,   # v6e/Trillium
    "TPU v6e": 918.0,
}


def _peak_tflops(dev) -> float:
    kind = dev.device_kind
    for k, v in _PEAK_TFLOPS.items():
        if kind.startswith(k):
            return v
    if dev.platform == "cpu":
        # harness smoke only (main() refuses the CPU unless the caller's
        # environment asked for it): MFU needs a denominator to print
        return _PEAK_TFLOPS["TPU v5e"]
    raise RuntimeError(
        f"no peak FLOP/s on record for device_kind {kind!r} (platform "
        f"{dev.platform!r}); add it to _PEAK_TFLOPS with its source — a "
        f"default would be an MFU against the wrong chip")


def _presets(backend: str, wide: bool = False):
    """(cfg, batch, seq). ``wide=False`` (the HEADLINE): LLaMA-7B shape
    ratios (I/E=2.6875, i.e. I=5504, L=12) at 738M params. ``wide=True``
    (secondary): the benchmark-friendly 4x-wide SwiGLU FFN (I=8192, L=8) —
    this chip's sustained matmul throughput is strongly K/N-width dependent
    (K=N=1024 caps at ~22 TF/s, wide contractions at ~85-171 of 197 peak),
    recorded to show the width effect, NOT as the headline."""
    from paddle_tpu.models.llama import LlamaConfig
    if backend != "tpu":
        # CPU smoke config — numbers are not meaningful, just keep the
        # harness runnable anywhere
        return LlamaConfig(vocab_size=1024, hidden_size=128,
                           intermediate_size=512 if wide else 384,
                           num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=4,
                           use_kernels=False, remat=False), 2, 256
    import jax.numpy as jnp
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048,
        intermediate_size=8192 if wide else 5504,
        num_hidden_layers=8 if wide else 12,
        num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=2048, use_kernels=True, remat=True,
        dtype=jnp.bfloat16, param_dtype=jnp.float32)
    return cfg, 8, 2048


def _train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """fwd+bwd matmul FLOPs: 6*N per token + causal attention term."""
    from paddle_tpu.models.llama import num_params
    n = num_params(cfg)
    tokens = batch * seq
    # causal attention: 12*L*E*S per token (QK^T + PV, fwd+bwd), halved by mask
    attn = 6 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return tokens * (6 * n + attn)


def bench_train(cfg, batch, seq, steps, lr=1e-4):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step_fn = llama.make_train_step(cfg, lr=lr)
    opt = init_opt(params)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    from paddle_tpu.jit.train_step import jit_step
    jstep = jit_step(step_fn, donate_argnums=(0, 1))

    # Timing protocol: wall time for `steps` dispatches closed by a float()
    # read of the final loss (a device->host read waits for the whole queue;
    # matches steady-state pipelined training, where dispatch runs ahead of
    # the device anyway).
    t0 = time.time()
    params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)
    compile_s = time.time() - t0

    for _ in range(2):  # warmup post-compile
        params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)  # drain

    t0 = time.time()
    for _ in range(steps):
        params, opt, loss = jstep(params, opt, ids, ids)
    final = float(loss)  # full-queue drain
    per_step = (time.time() - t0) / steps
    assert np.isfinite(final), f"loss diverged: {final}"
    return {"step_time_s": per_step, "compile_s": compile_s,
            "tokens_per_s": batch * seq / per_step,
            "loss": final}


def _loop_timed(grad_fn, q, k, v, iters):
    """Time fwd+bwd of ``grad_fn`` with the iteration loop INSIDE one
    compiled program (a lax.fori_loop with a scalar dependency chain), so
    per-dispatch overhead amortizes to nothing. Returns seconds per
    iteration."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(q, k, v):
        def body(i, carry):
            # serialize iterations WITHOUT promoting q's dtype (bf16 + f32
            # scalar would silently time an f32 kernel)
            qq = q + (carry * 1e-24).astype(q.dtype)
            g = grad_fn(qq, k, v)
            gs = g if isinstance(g, (tuple, list)) else (g,)
            # consume one element of EVERY grad: a dead grad output gets
            # DCE'd by XLA and its backward matmuls silently vanish from
            # the measurement (weight grads are half the bwd FLOPs)
            return sum(gg.ravel()[0].astype(jnp.float32) for gg in gs)
        return lax.fori_loop(0, iters, body, jnp.float32(0.0))

    f = jax.jit(run)
    float(f(q, k, v))                 # compile + warm
    t0 = time.time()
    out = float(f(q, k, v))
    per = (time.time() - t0) / iters
    assert np.isfinite(out)
    return per


def _median_fresh(grad_fn, q, k, v, iters, executables=3):
    """Median over N FRESH executables of the in-graph loop timing.

    XLA's compile-time autotuning makes per-executable times vary (the
    composed-SDPA side has been observed 1.0-1.75x run to run); a single
    executable can also be frozen bad by the persistent compile cache. A
    tiny static salt forces distinct cache keys -> distinct executables;
    the median is the variance-proof point estimate (r4 VERDICT weak #4 /
    next #2)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    times = []
    for salt in range(executables):
        def run(q, k, v, _salt=salt):
            def body(i, carry):
                qq = q + (carry * 1e-24).astype(q.dtype)
                g = grad_fn(qq, k, v)
                gs = g if isinstance(g, (tuple, list)) else (g,)
                # the salt must survive into the traced program as a
                # DISTINCT literal per executable, or every "fresh"
                # executable shares one cache key and this degenerates to
                # timing a single binary three times: embed it as a
                # value-irrelevant (1e-38-scaled) constant in the carry
                return sum(gg.ravel()[0].astype(jnp.float32)
                           for gg in gs) + jnp.float32(_salt) * 1e-38
            return lax.fori_loop(0, iters, body, jnp.float32(0.0))

        f = jax.jit(run)
        float(f(q, k, v))             # compile + warm
        t0 = time.time()
        out = float(f(q, k, v))
        times.append((time.time() - t0) / iters)
        assert np.isfinite(out)
    times.sort()
    return times[len(times) // 2], times


def bench_attention(seq=2048, batch=4, heads=16, head_dim=64, steps=10):
    """Pallas flash attention vs jnp SDPA reference, fwd+bwd, causal
    (iteration loop compiled in-graph — see _loop_timed)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import flash_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, head_dim)
    q = jax.random.normal(k1, shape, jnp.bfloat16)
    k = jax.random.normal(k2, shape, jnp.bfloat16)
    v = jax.random.normal(k3, shape, jnp.bfloat16)

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(head_dim)
        mask = jnp.tril(jnp.ones((seq, seq), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    results = {}
    for name, fn in (("flash", lambda q, k, v: flash_attention(q, k, v, causal=True)),
                     ("ref", ref)):
        g = jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
        med, all_t = _median_fresh(g, q, k, v, max(steps, 10))
        results[name] = med
        results[name + "_all"] = all_t
    return results


def bench_resnet(batch=32, steps=8, image=224, nhwc=False):
    """ResNet-50 train step through the fused donation-aware path
    (jit.train_step.make_train_step — forward+backward+Momentum update as
    one donated XLA program). ``nhwc=True`` runs the channels-last layout
    pass (nn.ChannelsLast) — the TPU-native conv layout; the delta vs the
    NCHW row is the tracked layout win (BASELINE.md ResNet-50 row)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit.train_step import make_train_step
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet50

    net = resnet50(num_classes=1000)
    if nhwc:
        net = nn.ChannelsLast(net)
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=net.parameters())
    # amp=True keeps the bf16 matmul/conv cast of the previous to_static
    # harness; donation is auto (on for TPU, off for the CPU smoke run)
    train_step = make_train_step(net, opt, nn.CrossEntropyLoss(), amp=True)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal(
        (batch, 3, image, image)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)).astype("int64"))

    # state-discovery warmup runs EAGERLY (the tape retains every
    # activation — no XLA buffer reuse), so do it on a tiny batch; the
    # timed batch size then compiles as its own signature
    xw = paddle.to_tensor(rng.standard_normal(
        (2, 3, image, image)).astype("float32"))
    yw = paddle.to_tensor(rng.integers(0, 1000, (2,)).astype("int64"))
    t0 = time.time()
    float(train_step(xw, yw))  # warmup eager pass (state discovery)
    warm_s = time.time() - t0
    t0 = time.time()
    float(train_step(x, y))  # compile at the timed batch size
    compile_s = time.time() - t0
    float(train_step(x, y))  # drain
    t0 = time.time()
    for _ in range(steps):
        loss = train_step(x, y)
    final = float(loss)
    per_step = (time.time() - t0) / steps
    assert np.isfinite(final)
    return {"images_per_s": batch / per_step, "step_time_s": per_step,
            "warmup_s": warm_s, "compile_s": compile_s, "loss": final}


def bench_bert(batch=32, seq=128, steps=8):
    """BERT-base fine-tune step via eager->to_static (BASELINE.md row)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import amp
    from paddle_tpu.jit import to_static
    from paddle_tpu.models.bert import BertConfig, BertForSequenceClassification
    from paddle_tpu.optimizer import AdamW

    cfg = BertConfig()  # base: L=12, H=768
    net = BertForSequenceClassification(cfg, num_classes=2)
    opt = AdamW(learning_rate=2e-5, parameters=net.parameters())
    rng = np.random.default_rng(0)

    @to_static
    def train_step(ids, labels):
        with amp.auto_cast():
            loss, _ = net(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def mk(b, s):
        return (paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                              (b, s)).astype("int64")),
                paddle.to_tensor(rng.integers(0, 2, (b,)).astype("int64")))

    xw, yw = mk(2, seq)
    t0 = time.time()
    float(train_step(xw, yw))  # eager state-discovery warmup (tiny batch)
    warm_s = time.time() - t0
    x, y = mk(batch, seq)
    t0 = time.time()
    float(train_step(x, y))    # compile at the timed size
    compile_s = time.time() - t0
    float(train_step(x, y))
    t0 = time.time()
    for _ in range(steps):
        loss = train_step(x, y)
    final = float(loss)
    per_step = (time.time() - t0) / steps
    assert np.isfinite(final)
    return {"examples_per_s": batch / per_step, "step_time_s": per_step,
            "warmup_s": warm_s, "compile_s": compile_s}


def bench_sdxl_attention(steps=10):
    """SDXL-UNet-shape attention blocks through the Pallas kernel
    (BASELINE.md row): the UNet's heavy self-attention at 64x64 latents
    (S=4096, H=10, D=64) and 32x32 (S=1024, H=20, D=64), fwd+bwd."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import flash_attention

    out = {}
    for name, (B, S, H, D) in {"sdxl_64x64": (2, 4096, 10, 64),
                               "sdxl_32x32": (2, 1024, 20, 64)}.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
                   for kk in ks)
        g = jax.grad(lambda q, k, v: flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        med, all_t = _median_fresh(g, q, k, v, max(steps, 10))
        out[name + "_ms"] = round(med * 1e3, 2)
        out[name + "_all_ms"] = [round(t * 1e3, 2) for t in all_t]
    return out


def bench_detect(batch=8, steps=8, image=320):
    """PP-YOLOE-style detector train step (MobileNetV3-small + FPN +
    decoupled head + center-assigned loss) through the fused
    donation-aware path (BASELINE.json configs[2] detection target)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn  # noqa: F401
    from paddle_tpu.jit.train_step import make_train_step
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.detection import detection_loss, ppyoloe_mbv3

    paddle.seed(0)
    det = ppyoloe_mbv3(num_classes=80, image_size=image)
    opt = Momentum(learning_rate=0.01, momentum=0.9,
                   parameters=det.parameters())
    pts, strides = det.anchor_points()
    rng = np.random.default_rng(0)

    step = make_train_step(
        det, opt,
        lambda cls, boxes, gt_b, gt_l: detection_loss(
            cls, boxes, gt_b, gt_l, pts, strides, 80),
        amp=True)

    def train_step(x, gt_b, gt_l):
        return step([x], [gt_b, gt_l])

    def mk(b):
        x = paddle.to_tensor(rng.standard_normal(
            (b, 3, image, image)).astype(np.float32))
        lo = rng.uniform(0, image - 64, (b, 4, 2)).astype(np.float32)
        wh = rng.uniform(16, 64, (b, 4, 2)).astype(np.float32)
        gt_b = paddle.to_tensor(np.concatenate([lo, lo + wh], -1))
        gt_l = paddle.to_tensor(
            rng.integers(0, 80, (b, 4)).astype(np.int32))
        return x, gt_b, gt_l

    xw, bw, lw = mk(2)
    t0 = time.time()
    float(train_step(xw, bw, lw))   # eager state-discovery warmup
    warm_s = time.time() - t0
    x, gb, gl = mk(batch)
    t0 = time.time()
    float(train_step(x, gb, gl))    # compile at the timed size
    compile_s = time.time() - t0
    float(train_step(x, gb, gl))
    t0 = time.time()
    for _ in range(steps):
        loss = train_step(x, gb, gl)
    final = float(loss)
    per_step = (time.time() - t0) / steps
    assert np.isfinite(final)
    return {"images_per_s": batch / per_step, "step_time_s": per_step,
            "warmup_s": warm_s, "compile_s": compile_s, "loss": final}


def bench_checkpoint(backend, steps=10):
    """Fault-tolerance cost tracking (docs/FAULT_TOLERANCE.md): (a) the
    async-save OVERLAP — per-step overhead while a checkpoint is in flight
    vs steady state on the llama preset (acceptance bound: < 15%); (b) the
    blocking device->host snapshot cost; (c) restore-verify latency (walk
    to newest committed, re-hash every shard, assemble + device_put)."""
    import os
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.checkpoint import AsyncCheckpointer
    from paddle_tpu.models import llama

    cfg, batch, seq = _presets(backend, wide=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step_fn = llama.make_train_step(cfg, lr=1e-4)
    opt = init_opt(params)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    from paddle_tpu.jit.train_step import jit_step
    jstep = jit_step(step_fn, donate_argnums=(0, 1))
    params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)                          # compile + drain
    for _ in range(2):
        params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)

    it = max(steps, 10)
    t0 = time.time()
    for _ in range(it):
        params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)
    steady = (time.time() - t0) / it

    # leaves snapshot specs BEFORE the overlap loop donates the buffers
    leaves = jax.tree_util.tree_leaves(params)
    specs = [(a.shape, a.dtype) for a in leaves]
    state = {f"p{i}": a for i, a in enumerate(leaves)}
    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        ck = AsyncCheckpointer(root, keep_last_k=2)
        t0 = time.time()
        ck.save(state, 0)                # sync device->host + async write
        snapshot_s = time.time() - t0
        t0 = time.time()
        for _ in range(it):              # the save drains UNDER this loop
            params, opt, loss = jstep(params, opt, ids, ids)
        float(loss)
        during = (time.time() - t0) / it
        in_flight_after = ck.is_saving   # False = write finished early
        ck.wait()
        overhead_pct = 100.0 * (during - steady) / steady

        dst = {f"p{i}": jnp.zeros(sh, dt) for i, (sh, dt)
               in enumerate(specs)}
        t0 = time.time()
        got = ck.restore(dst)            # verify checksums + assemble
        restore_s = time.time() - t0
        assert got == 0, got
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(root) for f in fs)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {"steady_step_s": round(steady, 4),
            "during_save_step_s": round(during, 4),
            "overhead_pct": round(overhead_pct, 2),
            "snapshot_block_s": round(snapshot_s, 4),
            "save_outlived_loop": bool(in_flight_after),
            "restore_verify_ms": round(restore_s * 1e3, 1),
            "ckpt_mb": round(ckpt_bytes / 2**20, 1)}


def bench_input(backend, batch=32, image=224, nbatches=16):
    """Input-pipeline bench (docs/PERFORMANCE.md): (a) H2D transfer cost
    per batch (blocking device_put of an imagenet-shaped batch), (b) the
    overlap won by ``prefetch_to_device`` — serial (transfer, then step)
    vs pipelined (transfers in flight under the running step) over the
    same synthetic batches and a fixed device workload. ``overlap_pct`` is
    the fraction of total H2D time hidden by the pipeline; on CPU (no real
    transfer, single-buffer fallback) it is ~0 by design."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.io.dataloader import prefetch_to_device

    if backend != "tpu":
        batch, image, nbatches = 8, 64, 8   # CPU smoke: keep it instant
    rng = np.random.default_rng(0)
    batches = [rng.standard_normal((batch, 3, image, image))
               .astype(np.float32) for _ in range(nbatches)]

    # fixed device workload standing in for a train step (a few chained
    # matmuls over the flattened batch — enough device time to hide
    # transfers behind)
    k = image * image * 3
    w = jnp.asarray(rng.standard_normal((k, 256)).astype(np.float32))

    def stepfn(x, w):
        h = x.reshape(x.shape[0], -1) @ w
        for _ in range(4):
            h = jnp.tanh(h) @ (w[:256, :256] if w.shape[0] >= 256 else w.T @ w)
        return h.sum()
    jstep = jax.jit(stepfn)
    x0 = jax.device_put(batches[0])
    float(jstep(x0, w))                      # compile + warm

    # (a) blocking H2D per batch
    t0 = time.time()
    for b in batches:
        jax.block_until_ready(jax.device_put(b))
    h2d_ms = (time.time() - t0) / nbatches * 1e3

    # (b) serial: transfer then step, one batch at a time
    t0 = time.time()
    for b in batches:
        xb = jax.device_put(b)
        r = jstep(xb, w)
    float(r)
    serial_s = time.time() - t0

    # (c) pipelined: prefetch_to_device keeps transfers in flight
    t0 = time.time()
    for tb in prefetch_to_device(batches, size=2):
        r = jstep(tb._raw, w)
    float(r)
    overlap_s = time.time() - t0

    h2d_total = h2d_ms / 1e3 * nbatches
    hidden = max(0.0, serial_s - overlap_s)
    overlap_pct = 100.0 * min(hidden / h2d_total, 1.0) if h2d_total else 0.0
    return {"h2d_ms_per_batch": round(h2d_ms, 3),
            "serial_s": round(serial_s, 4),
            "pipelined_s": round(overlap_s, 4),
            "overlap_pct": round(overlap_pct, 1),
            "batch": batch, "image": image}


def bench_tuned(backend, peak, steps=10, batch=8, seq=2048):
    """The memory-tuned LLaMA-ratio point (secondary; the headline keeps the
    reference-parity numerics): remat_policy="save_flash" (flash residuals +
    qkv saved — backward never re-runs the fwd attention kernel or the qkv
    matmuls), token-chunked CE, bf16 Adam-moment STORAGE and bf16 grad
    STORAGE (fp32 moment arithmetic; the weight grads are produced by bf16
    backward matmuls anyway). Each trade is a storage-precision knob, and
    they buy the HBM headroom the faster remat schedule needs. Measured
    r4: 56.4% vs the honest default's 52.9%."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama

    cfg, b, s = _presets(backend, wide=False)
    batch, seq = batch or b, seq or s
    if backend == "tpu":
        cfg = dataclasses.replace(cfg, remat_policy="save_flash",
                                  ce_chunks=16)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step_fn = llama.make_train_step(
        cfg, lr=1e-4, opt_dtype=jnp.bfloat16, grad_dtype=jnp.bfloat16)
    opt = init_opt(params)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    from paddle_tpu.jit.train_step import jit_step
    jstep = jit_step(step_fn, donate_argnums=(0, 1))
    params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)
    for _ in range(2):
        params, opt, loss = jstep(params, opt, ids, ids)
    float(loss)
    t0 = time.time()
    for _ in range(steps):
        params, opt, loss = jstep(params, opt, ids, ids)
    final = float(loss)
    per_step = (time.time() - t0) / steps
    assert np.isfinite(final)
    flops = _train_flops_per_step(cfg, batch, seq)
    return 100.0 * flops / per_step / 1e12 / peak, per_step


def bench_health(backend, peak, steps=10):
    """Run-health sentinel cost (docs/FAULT_TOLERANCE.md "Runtime
    anomalies"): the tuned llama row with and without the on-device
    NaN/Inf detector fused into the donated step
    (llama.make_train_step(sentinel=True) — the bad-step gate rides
    inside the AdamW update via _adamw_apply(skip=bad): one fused grad
    mask + scalar decay/LR selects, plus the packed [loss, bad, ema]
    health vector; the generic output-side health.guard_step wrapper
    costs an extra select pass per buffer and is measured by
    tests/test_health.py instead). Acceptance bound: overhead <= 2%.
    Also proves containment end to end: one
    NaN-poisoned step must flag bad=1 AND leave the optimizer state
    un-advanced (step counter frozen, moments finite)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from paddle_tpu import health
    from paddle_tpu.jit.train_step import jit_step
    from paddle_tpu.models import llama

    cfg, batch, seq = _presets(backend, wide=False)
    if backend == "tpu":
        cfg = dataclasses.replace(cfg, remat_policy="save_flash",
                                  ce_chunks=16)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step_fn = llama.make_train_step(cfg, lr=1e-4)
    opt = init_opt(params)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)

    def timed(jfn, state, n):
        """Warmup/drain/timing protocol shared by BOTH rows (any drift
        between them would skew the overhead_pct the 2% bound rests on).
        ``state`` is the tuple of leading state args threaded through the
        step; the trailing output is the loss/health scalar drained for
        sync."""
        k = len(state)
        out = None
        for _ in range(2):
            out = jfn(*state, ids, ids)
            state = out[:k]
        float(jax.tree_util.tree_leaves(out[-1])[0].ravel()[0])  # drain
        t0 = time.time()
        for _ in range(n):
            out = jfn(*state, ids, ids)
            state = out[:k]
        float(jax.tree_util.tree_leaves(out[-1])[0].ravel()[0])
        return (time.time() - t0) / n, out, state

    it = max(steps, 10)
    jbase = jit_step(step_fn, donate_argnums=(0, 1))
    params2 = llama.init_params(cfg, jax.random.PRNGKey(0))
    _, gstep_fn = llama.make_train_step(cfg, lr=1e-4, sentinel=True)
    opt2 = init_opt(params2)
    jguard = jit_step(gstep_fn, donate_argnums=(0, 1, 2))

    # Host-load noise on a busy machine dwarfs the 2% bound, so two
    # monolithic back-to-back blocks can't measure it — and load spikes
    # are SHORTER than a block, so pairing adjacent blocks doesn't cancel
    # them either (a median-of-ratios reads pure noise). Interleave many
    # small blocks of each variant and take each one's MIN per-step time:
    # the least-contended block estimates the variant's uncontended cost,
    # which is the quantity the 2% bound is about.
    rounds, n = 8, max(2, it // 2)
    state_b = (params, opt)
    state_g = (params2, opt2, health.sentinel_init())
    base_s = guard_s = float("inf")
    out = None
    for _ in range(rounds):
        b, _, state_b = timed(jbase, state_b, n)
        g, out, state_g = timed(jguard, state_g, n)
        base_s = min(base_s, b)
        guard_s = min(guard_s, g)
    p, o, sent = state_g
    loss, bad, ema = health.unpack_health(out[-1])
    assert not bad and np.isfinite(loss), (loss, bad)
    overhead_pct = 100.0 * (guard_s - base_s) / base_s

    # containment proof: a NaN-poisoned step must be flagged bad AND
    # gated — the AdamW step counter must not advance and the moments
    # must stay finite (an applied NaN update would poison both). The ids
    # are ints and can't carry NaN, so the poison rides the params (chaos
    # nan_payload's fault model applied to the weight buffers). Counter
    # read happens BEFORE the call: the call donates o's buffers.
    step_before = int(o["step"])
    p2, o2, sent2, h2 = jguard(
        jax.tree_util.tree_map(lambda a: (a * jnp.float32(np.nan)).astype(
            a.dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, p),
        o, sent, ids, ids)
    _, bad2, _ = health.unpack_health(h2)
    moments_finite = all(
        bool(jnp.isfinite(a).all())
        for tree in (o2["m"], o2["v"])
        for a in jax.tree_util.tree_leaves(tree))
    contained = int(o2["step"]) == step_before and moments_finite
    return {"base_step_s": round(base_s, 4),
            "sentinel_step_s": round(guard_s, 4),
            "overhead_pct": round(overhead_pct, 2),
            "nan_step_flagged": bool(bad2),
            "nan_step_contained": contained,
            "loss": round(loss, 3)}


def bench_roofline(backend, steps=10):
    """Phase-isolated timing of the HEADLINE config's train step (r3 VERDICT
    #3): each term measured as its own in-graph loop (same _loop_timed
    protocol), so the decomposition can be compared against the observed
    step time and the MFU gap attributed. Emits one JSON object to stderr;
    numbers land in BASELINE.md's roofline table."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.models import llama

    cfg, B, S = _presets(backend, wide=False)
    E, I, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_hidden_layers, cfg.vocab_size)
    H, D = cfg.num_attention_heads, cfg.head_dim
    T = B * S
    k = jax.random.PRNGKey(0)
    out = {}

    def timed(name, grad_fn, *arrs, iters=None):
        it = iters or max(steps, 10)
        per = _loop_timed(grad_fn, *arrs, iters=it)
        out[name + "_ms"] = round(per * 1e3, 3)
        return per

    def g3(f):
        # loss = |out|^2, NOT sum(out): a linear functional lets XLA's
        # algebraic simplifier collapse trailing matmuls to matvecs (sum(A@B)
        # = A @ (B@1)) — measured 227 "TF/s" (> peak) before this fix
        def loss(a, b, c):
            o = f(a, b, c).astype(jnp.float32)
            return jnp.vdot(o, o)
        return jax.grad(loss, argnums=(0, 1, 2))

    # ---- attention (flash kernel, causal), fwd+bwd, ONE layer -------------
    q = jax.random.normal(k, (B, S, H, D), jnp.bfloat16)
    timed("attn_layer", g3(lambda q, kk, v: flash_attention(
        q, kk, v, causal=True)), q, q, q)

    # ---- FFN (SwiGLU), fwd+bwd, ONE layer ---------------------------------
    h = jax.random.normal(k, (T, E), jnp.bfloat16)
    wg = jax.random.normal(jax.random.fold_in(k, 1), (E, 2 * I),
                           jnp.bfloat16)           # gate+up fused [E, 2I]
    wd = jax.random.normal(jax.random.fold_in(k, 2), (I, E), jnp.bfloat16)

    def ffn(h, wg, wd):
        gu = h @ wg                                # one [E,2I] matmul
        gate = jax.nn.silu(gu[:, :I]) * gu[:, I:]
        return gate @ wd
    timed("ffn_layer", g3(ffn), h, wg, wd)

    # ---- QKV+O projections, fwd+bwd, ONE layer ----------------------------
    wqkv = jax.random.normal(jax.random.fold_in(k, 3), (E, 3 * E),
                             jnp.bfloat16)
    wo = jax.random.normal(jax.random.fold_in(k, 4), (E, E), jnp.bfloat16)

    def qkvo(h, wqkv, wo):
        y = h @ wqkv
        return (y[:, :E] + y[:, E:2 * E] + y[:, 2 * E:]) @ wo
    timed("qkvo_layer", g3(qkvo), h, wqkv, wo)

    # ---- fwd-only flavors (= the remat recompute cost per layer) ----------
    def fwd_loop(f, *arrs):
        def run(*a):
            def body(i, carry):
                a0 = a[0] + (carry * 1e-24).astype(a[0].dtype)
                r = f(a0, *a[1:]).astype(jnp.float32)
                return jnp.vdot(r, r)   # consume the FULL output (no DCE)
            return lax.fori_loop(0, max(steps, 10), body, jnp.float32(0.0))
        fjit = jax.jit(run)
        float(fjit(*arrs))
        t0 = time.time()
        float(fjit(*arrs))
        return (time.time() - t0) / max(steps, 10)

    out["attn_layer_fwd_ms"] = round(fwd_loop(
        lambda q, kk, v: flash_attention(q, kk, v, causal=True),
        q, q, q) * 1e3, 3)
    out["ffn_layer_fwd_ms"] = round(fwd_loop(ffn, h, wg, wd) * 1e3, 3)
    out["qkvo_layer_fwd_ms"] = round(fwd_loop(qkvo, h, wqkv, wo) * 1e3, 3)

    # ---- embedding + LM head + CE, fwd+bwd --------------------------------
    emb = jax.random.normal(k, (V, E), jnp.float32)
    ids = jax.random.randint(k, (B, S), 0, V)

    def embed_ce(emb, hd, _):
        x = jnp.take(emb, ids, axis=0).astype(jnp.bfloat16)
        logits = (x @ hd.astype(jnp.bfloat16)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
        return (lse - tgt).mean()[None]
    hd = jax.random.normal(k, (E, V), jnp.float32)
    timed("embed_ce", g3(embed_ce), emb, hd, emb)

    # ---- optimizer (AdamW fp32, donated state) ----------------------------
    params = llama.init_params(cfg, k)
    from paddle_tpu.models.llama import _adamw_apply, _adamw_init
    opt0 = _adamw_init(params)
    grads = jax.device_put(jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-6, p.dtype), params))

    def adam_step(params, opt, grads):   # grads as an ARG (a captured-const
        # closure embeds 2.95GB into the executable and skews the timing)
        return _adamw_apply(params, grads, opt, lr=1e-4, beta1=0.9,
                            beta2=0.95, eps=1e-8, weight_decay=0.0,
                            opt_dtype=jnp.float32)
    jadam = jax.jit(adam_step, donate_argnums=(0, 1))
    p, o = jadam(params, opt0, grads)
    jax.block_until_ready(p)
    t0 = time.time()
    for _ in range(max(steps, 10)):
        p, o = jadam(p, o, grads)
    float(p["ln_f"][0])
    out["adam_full_ms"] = round(
        (time.time() - t0) / max(steps, 10) * 1e3, 3)

    # ---- model: account -----------------------------------------------
    acct = {
        "attn_bwd_x_L": out["attn_layer_ms"] * L,
        "ffn_bwd_x_L": out["ffn_layer_ms"] * L,
        "qkvo_bwd_x_L": out["qkvo_layer_ms"] * L,
        "remat_recompute_x_L": (out["attn_layer_fwd_ms"]
                                + out["ffn_layer_fwd_ms"]
                                + out["qkvo_layer_fwd_ms"]) * L,
        "embed_ce": out["embed_ce_ms"],
        "adam": out["adam_full_ms"],
    }
    acct["sum_ms"] = round(sum(acct.values()), 1)
    out["account"] = {kk: round(vv, 1) for kk, vv in acct.items()}
    return out


def bench_decode(backend, prompt=128, new_tokens=128, batches=(1, 8),
                 int8: bool = False):
    """KV-cache decode throughput on the flagship config (BASELINE.md decode
    row): prefill + the whole greedy decode loop is ONE compiled program
    (models/generation.py); reports decode tokens/s at each batch size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import generation as G
    from paddle_tpu.models.llama import init_params

    cfg, _, _ = _presets(backend, wide=False)
    # decode is HBM-bandwidth bound, not MXU bound: flash kernel + remat are
    # training knobs; the cache path uses plain jnp attention
    params = init_params(cfg, jax.random.PRNGKey(0))
    if int8:
        from paddle_tpu.models.llama import quantize_params
        params = quantize_params(params)
    rng = np.random.default_rng(0)
    out = {}
    short = max(2, new_tokens // 16)
    for B in batches:
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, prompt)),
                          jnp.int32)
        plens = jnp.full((B,), prompt, jnp.int32)
        key = jax.random.PRNGKey(0)
        # one fn() call = prefill + the decode scan; isolate the PURE decode
        # rate by differencing a long and a short decode at the same prompt
        # (both include one identical prefill)
        times = {}
        for n in (short, new_tokens):
            fn = jax.jit(G.make_generate_fn(cfg, max_new_tokens=n))
            t0 = time.time()
            toks = fn(params, ids, plens, key)
            int(toks[0, -1])  # device->host read = the only reliable sync
            times[f"compile_{n}"] = time.time() - t0
            t0 = time.time()
            toks = fn(params, ids, plens, key)
            int(toks[0, -1])
            times[n] = time.time() - t0
        dt = times[new_tokens] - times[short]     # pure decode, n-short toks
        per_tok = dt / (new_tokens - short)
        out[f"decode_b{B}_tok_s"] = round(B / per_tok, 1)
        out[f"decode_b{B}_ms_per_tok"] = round(per_tok * 1e3, 2)
        out[f"decode_b{B}_e2e_s"] = round(times[new_tokens], 3)
        out[f"decode_b{B}_compile_s"] = round(times[f"compile_{new_tokens}"], 1)
    return out


def bench_serve(backend):
    """Continuous-batching serving vs the static-batch baseline
    (docs/SERVING.md; ISSUE 4 acceptance): replay a mixed prompt/output-
    length request trace through (a) the static path — arrival-order
    batches of ``max_slots`` padded to the batch max prompt and decoded to
    the batch max output length (one compiled program per batch, the
    pre-serving deployment story) and (b) the ServingEngine — paged KV
    cache, iteration-level retire/admit, schedule-sized decode dispatches.
    Both sides run a warm pass first so compiles stay out of the timing,
    then 5 INTERLEAVED timed rounds each; the reported speedup is the
    MEDIAN of per-round ratios (adjacent runs share the host-load window,
    so each ratio is drift-immune) and tok/s are per-side medians; the
    static pass's outputs double as the dense-cache parity oracle
    (``outputs_match``) and the engine's trace counter proves the decode
    executable count stays constant across the trace
    (``recompiles_constant``). Reports aggregate tok/s both sides, the
    speedup (acceptance bound: >= 1.5x), and p50/p99 TTFT / per-token
    latency. The mixed-trace engine runs with the prefix cache OFF so the
    row keeps measuring SCHEDULING (on-demand paging + continuous
    batching) — repeat timed rounds replay identical prompts, and cache
    hits would flatter the comparison.

    Two ISSUE 5 rows ride along: a SHARED-PREFIX trace (every request
    opens with the same system-prompt prefix) timed with the prefix cache
    on vs off — interleaved rounds, speedup = median of per-round ratios,
    acceptance bound >= 1.3x — and a PREEMPTION-PRESSURE trace (pool
    sized well below the slots' worst-case budgets) that must complete
    bit-identical to the dense oracle with at least one preemption.

    The ISSUE 6 OVERLOAD row replays one 2x-capacity burst through the
    status-quo FIFO engine and through EDF with per-request TTFT SLOs
    (calibrated to the measured FIFO makespan) + deadline shedding:
    EDF must beat FIFO on p99 TTFT over served requests (asserted), at
    least one request must be shed (asserted), every served output must
    bit-match the dense oracle (asserted), and goodput (SLO-met tokens/s)
    is reported for the driver round — not asserted in-section, since the
    shed volume tracks wall-clock against FIFO-calibrated SLOs and a
    loaded host swings it either way.

    The ISSUE 7 FRONT-LINE row serves a mini trace through the asyncio
    server (in-process transport) with an ``engine_crash`` injected
    mid-trace: the supervisor must restart the engine (no recompile —
    shared EnginePrograms), resubmit, keep every stream bit-identical to
    the dense oracle, and drain with zero leaked blocks (all asserted);
    the overload burst above must additionally register as a scale-up on
    the autoscale hook (asserted).

    Two ISSUE 10 rows: a LONG-CONTEXT decode row (tok/s vs context
    length, the Pallas flash-decoding paged-attention kernel vs the
    gather fallback — token-exact across paths and compile-once both
    asserted; on CPU the kernel runs interpret mode, so the numbers
    there prove correctness, not speed) and a KV CAPACITY row (one byte
    budget split into an fp pool and an int8 pool — the int8 layout must
    admit >= 2x the concurrent sequences, asserted, with exact
    length/EOS parity and >= 0.6 token agreement on the served trace —
    greedy argmax under int8 quantization noise flips occasionally and a
    flipped token forks the remaining stream, so the trace-level bound is
    deliberately loose; observed ~0.83 on CPU, with the tight per-dispatch
    logit bound pinned in tests/test_serving.py).

    The ISSUE 11 SPEC-DECODE row sweeps acceptance rate: a
    high-acceptance trace (self-continuation prompts — the n-gram
    prompt-lookup drafter hits the stream's own cycles, so each
    multi-query verify dispatch retires several tokens) vs a
    low-acceptance trace (incoherent random prompts — no n-gram
    reoccurs, every step falls through to the plain decode loop).
    Asserted: spec output bit-identical to plain greedy decode on BOTH
    traces, drafts accepted on the high trace, ONE verify executable,
    zero blocks in use after rollback, and the low-acceptance ratio
    >= 0.9x (bounded drafting overhead). The high-acceptance speedup is
    emitted as serving_spec_speedup (anchor = the 1.3x acceptance
    bound).

    The ISSUE 9 FLEET row serves a trace through a 2-replica
    ServingRouter (both replicas sharing the overload row's compiled
    programs) with ``replica_kill`` fired mid-trace: the router must fail
    every in-flight request over to the healthy replica (failovers >= 1)
    with outputs bit-identical to the dense oracle and zero router-failed
    requests, every replica's pool must end with zero blocks in use, and
    a ROLLING RESTART across the fleet — serving a second live trace —
    must complete with zero failed requests and bit-exact outputs while
    the shared-programs trace counter stays flat (all asserted).

    The ISSUE 13 REPLAY row drives a deterministic workload (diurnal
    arrivals, Zipf tenants, shared-prefix families, sampled rows, client
    cancels/disconnects/abandons, shed clients retrying with backoff)
    through an AUTOSCALING fleet under a seeded chaos timeline, with the
    InvariantAuditor sampling throughout and exhaustive at quiesce: zero
    violations, failed == 0, zero leaks, >= 1 autoscale spawn AND drain,
    and — against the same manifest on a FIXED fleet — a lower
    step-indexed arrival->first-token p99 and makespan (the measured
    autoscale effect; deterministic, so assertable). Emits
    serving_replay_goodput (SLO-met tokens/s per chip) plus the
    capacity-planning sizing line.

    Two ISSUE 16 rows: a KV TIERING row (a prefix-family re-visit trace
    through a device pool sized well below the families' combined
    working set — with the host-RAM offload tier ON, churn-evicted
    prefix chains swap to bounded host memory and the re-visit wave
    readmits them H2D as prefix hits with zero recompute; with the tier
    OFF the same wave re-prefills from scratch; bit parity both ways is
    asserted and the re-visit TTFT ratio off/on is the
    serving_tier_hit_ttft_ratio metric) and a MIGRATION row (a scale-in
    drain through a 2-replica router with live KV migration ON: every
    in-flight request on the drained replica must move — block chains +
    resolved decode state — to the survivor and finish bit-identically
    with recomputed_tokens == 0 and zero leaks; the prefill+decode
    tokens that did NOT have to be recomputed are the
    serving_migration_recompute_saved metric).

    Two ISSUE 17 rows: a FLEET-CACHE row (prefix families re-visited
    from the NON-holder replica — island caches re-prefill, the fleet
    directory pulls the chain's blocks cross-replica with CRC checks at
    both ends; the pinned re-visit TTFT ratio off/on is the
    serving_fleet_cache_hit_ttft_ratio metric) and a DISAGGREGATION row
    (a chat stream sharing the fleet with long prompts at equal chip
    count, unified 2-decode vs 1-decode + 1-prefill with the finished
    chain handed off via the adopt path at recomputed_tokens == 0; the
    chat p99 TPOT ratio unified/disagg is the serving_disagg_tpot_ratio
    metric)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import ServingConfig, ServingEngine
    from paddle_tpu.models import generation as G
    from paddle_tpu.models import llama

    # long-tailed output lengths (the realistic regime: most requests are
    # short, a quarter run long) — static batching pays every batch's max
    if backend == "tpu":
        cfg, _, _ = _presets(backend, wide=False)
        n_req, max_slots, blk, mlen, chunk = 32, 8, 16, 256, 8
        p_choices, o_choices = [32, 64, 96, 128], [8, 16, 32, 128]
    else:
        # CPU smoke: same structure, but NOT the shared tiny preset — at
        # hidden 128 the paged step's fixed op-count overhead (gather/
        # scatter/masks, ~1ms on XLA:CPU) is 2x the matmul work and buries
        # the scheduling win; at hidden 256 the per-iteration costs match
        # (measured 4.9ms static vs 4.4ms paged) and the comparison
        # exercises the same regime the TPU config runs in. Output lengths
        # 2-64 (25% long): the static path pays each batch's max (~256
        # decode iterations on this trace) while the engine's makespan is
        # ~136 — that iteration gap, not per-step costs, is what's measured
        from paddle_tpu.models.llama import LlamaConfig
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=768, num_hidden_layers=3,
                          num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=128)
        n_req, max_slots, blk, mlen, chunk = 16, 4, 8, 88, 4
        p_choices, o_choices = [8, 12, 16, 24], [2, 4, 8, 64]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    plens = rng.choice(p_choices, n_req)
    outs = rng.choice(o_choices, n_req)
    prompts = [rng.integers(0, cfg.vocab_size, (int(s),)).astype(np.int32)
               for s in plens]
    total_tokens = int(np.sum(outs))

    # ---- static-batch baseline (the dense-cache parity oracle) ----------
    def run_static():
        got, ttfts = [], []
        t0 = time.time()
        for i0 in range(0, n_req, max_slots):
            i1 = min(i0 + max_slots, n_req)
            pl, on = plens[i0:i1], outs[i0:i1]
            S, n = int(pl.max()), int(on.max())
            ids = np.zeros((i1 - i0, S), np.int32)
            for r in range(i0, i1):
                ids[r - i0, :plens[r]] = prompts[r]
            toks = np.asarray(G.generate(
                params, jnp.asarray(ids), cfg, max_new_tokens=n,
                prompt_lens=jnp.asarray(pl, jnp.int32)))
            t_batch = time.time() - t0     # first token lands with the batch
            for r in range(i1 - i0):
                got.append(toks[r, :on[r]])
                ttfts.append(t_batch)
        return got, ttfts, time.time() - t0

    def run_serving(engine):
        t0 = time.time()
        rids = [engine.submit(p, max_new_tokens=int(o), eos_token_id=None)
                for p, o in zip(prompts, outs)]
        while engine.pending:
            engine.step()
        return [engine.request(r) for r in rids], time.time() - t0

    engine = ServingEngine(params, cfg, ServingConfig(
        block_size=blk, max_slots=max_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=n_req, prefix_cache=None))
    run_static()                                           # warm/compile
    run_serving(engine)                                    # warm/compile
    traces_before = engine.stats()["decode_traces"]
    # INTERLEAVED rounds, speedup = MEDIAN of per-round ratios: adjacent
    # static/serving runs see the same host-load window, so each round's
    # ratio is drift-immune, and the median absorbs spike rounds. A
    # min-of-each-side would compare each side's luckiest window — windows
    # the other side may never have gotten (same lesson as bench --health's
    # interleaving; monolithic blocks drift apart)
    rounds = []
    for _ in range(5):
        static_out, static_ttft, st_s = run_static()
        reqs, sv_s = run_serving(engine)
        rounds.append((st_s, sv_s))
    static_s = float(np.median([r[0] for r in rounds]))
    serving_s = float(np.median([r[1] for r in rounds]))
    speedup = float(np.median([st / sv for st, sv in rounds]))
    static_tok_s = total_tokens / static_s
    serving_tok_s = total_tokens / serving_s
    serve_ttft = [r.ttft_s for r in reqs]
    serve_lat = [r.tok_latency_s for r in reqs
                 if r.tok_latency_s is not None]

    def pct(xs, q):
        return round(float(np.percentile(np.asarray(xs) * 1e3, q)), 2)

    match = all((np.asarray(r.output()) == s).all()
                for r, s in zip(reqs, static_out))
    st = engine.stats()

    # ---- shared-prefix trace: prefix cache ON vs OFF --------------------
    # every request opens with the same system-prompt prefix; the cached
    # engine maps the prefix blocks and prefills only each request's
    # unique tail, the uncached one re-runs the whole prompt every time.
    # Same interleaved median-of-ratios methodology as the mixed row.
    # the prefix must be LONG relative to the unique tail and the decode
    # budget: the row measures prefill-work-avoided, and a short prefix's
    # savings drown in the per-admission chunk-dispatch overhead (measured
    # 0.97x at prefix 48 on CPU vs 1.4-1.7x at prefix 112)
    if backend == "tpu":
        pre_len, uniq, n_pre, pre_out, pre_slots = 160, 16, 16, 8, 8
        pre_mlen = mlen
    else:
        pre_len, uniq, n_pre, pre_out, pre_slots = 112, 8, 12, 4, 4
        pre_mlen = 128                   # the mixed row's 88 can't hold it
    prefix = rng.integers(0, cfg.vocab_size, (pre_len,)).astype(np.int32)
    pre_prompts = [np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, (uniq,)).astype(np.int32)])
        for _ in range(n_pre)]
    pre_ids = np.stack(pre_prompts)
    pre_oracle = np.asarray(G.generate(params, jnp.asarray(pre_ids), cfg,
                                       max_new_tokens=pre_out))

    def mk_prefix_engine(on):
        return ServingEngine(params, cfg, ServingConfig(
            block_size=blk, max_slots=pre_slots, max_model_len=pre_mlen,
            decode_chunk=chunk, queue_depth=n_pre,
            prefix_cache=True if on else None))

    def run_prefix(eng):
        t0 = time.time()
        outs = eng.run(pre_prompts, max_new_tokens=pre_out,
                       eos_token_id=None)
        return outs, time.time() - t0

    eng_pc, eng_nc = mk_prefix_engine(True), mk_prefix_engine(False)
    run_prefix(eng_nc)                          # warm/compile
    pc_out, _ = run_prefix(eng_pc)              # warm/compile + cache fill
    pre_match = all((np.asarray(o) == pre_oracle[i]).all()
                    for i, o in enumerate(pc_out))
    pre_rounds = []
    for _ in range(5):
        _, nc_s = run_prefix(eng_nc)
        _, pc_s = run_prefix(eng_pc)
        pre_rounds.append((nc_s, pc_s))
    prefix_speedup = float(np.median([a / b for a, b in pre_rounds]))
    pre_tokens = n_pre * pre_out
    prefix_tok_s = pre_tokens / float(np.median(
        [b for _, b in pre_rounds]))
    pst = eng_pc.stats()

    # ---- preemption-pressure trace --------------------------------------
    # pool sized well below the slots' worst-case budgets: reservation
    # would have serialized these; on-demand paging runs them concurrently
    # and preempt-and-recompute keeps outputs BIT-IDENTICAL — the row's
    # proof is parity + at least one preemption, not a timing
    if backend == "tpu":
        pp_plen, pp_out, pp_n, pp_slots, pp_blocks = 32, 96, 12, 8, 8 * 5
    else:
        pp_plen, pp_out, pp_n, pp_slots, pp_blocks = 16, 40, 8, 4, 18
    pp_prompts = [rng.integers(0, cfg.vocab_size,
                               (pp_plen,)).astype(np.int32)
                  for _ in range(pp_n)]
    pp_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(pp_prompts)), cfg, max_new_tokens=pp_out))
    eng_pp = ServingEngine(params, cfg, ServingConfig(
        block_size=blk, max_slots=pp_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=pp_n, num_blocks=pp_blocks,
        prefix_cache=None))
    pp_out_toks = eng_pp.run(pp_prompts, max_new_tokens=pp_out,
                             eos_token_id=None)
    pp_match = all((np.asarray(o) == pp_oracle[i]).all()
                   for i, o in enumerate(pp_out_toks))
    ppst = eng_pp.stats()

    # ---- long-context decode row: Pallas kernel vs gather path (ISSUE 10)
    # the flash-decoding paged-attention kernel consumes block tables
    # IN-KERNEL (no [slots, W*bs, ...] gather is materialized) with GQA
    # grouped per kv head and int8 dequant fused into the block loads; the
    # gather + _masked_sdpa path stays as the oracle and runtime fallback
    # (FLAGS_serving_paged_kernel). tok/s at two context lengths, both
    # paths — on TPU the kernel is the bandwidth win at long context; on
    # CPU it runs in Pallas INTERPRET mode (the same kernel tier-1
    # exercises), so the CPU numbers prove parity + compile-once, not
    # speed. In-row asserts: token streams bit-equal across paths at
    # every context length, ONE decode trace per engine.
    if backend == "tpu":
        lc_ctxs, lc_out, lc_n = [256, 1024], 16, 4
        lc_mlen = 2048
    else:
        lc_ctxs, lc_out, lc_n = [32, 80], 8, 2
        lc_mlen = mlen
    lc_match, lc_traces_ok = True, True
    lc_rows = {}
    lc_engines = {path: ServingEngine(params, cfg, ServingConfig(
        block_size=blk, max_slots=2, max_model_len=lc_mlen,
        decode_chunk=chunk, queue_depth=lc_n, prefix_cache=None,
        paged_kernel=(path == "kernel")))
        for path in ("gather", "kernel")}
    for ctx in lc_ctxs:
        lc_prompts = [rng.integers(0, cfg.vocab_size, (ctx,))
                      .astype(np.int32) for _ in range(lc_n)]
        outs_by_path = {}
        for path, eng_lc in lc_engines.items():
            eng_lc.run(lc_prompts, max_new_tokens=2,
                       eos_token_id=None)               # warm/compile
            t0 = time.time()
            outs_by_path[path] = eng_lc.run(lc_prompts,
                                            max_new_tokens=lc_out,
                                            eos_token_id=None)
            lc_rows[f"longctx_{path}_tok_s_ctx{ctx}"] = round(
                lc_n * lc_out / (time.time() - t0), 1)
        lc_match &= all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(outs_by_path["kernel"], outs_by_path["gather"]))
    lc_traces_ok = all(e.stats()["decode_traces"] == 1
                       for e in lc_engines.values())

    # ---- KV capacity row: int8 pool vs fp at a FIXED byte budget --------
    # int8 KV blocks + per-token-per-head scales cost (D+4)/(4D) the bytes
    # of fp32 — the SAME budget holds ~3.5x the blocks, so admissions,
    # prefix-cache capacity and preemption headroom all multiply. The row
    # sizes both pools to one byte budget, reports max concurrent
    # sequences (static arithmetic + the live peak observed on a real
    # trace), and proves the quantized pool serves: exact per-request
    # LENGTH parity vs the fp engine, token agreement >= 0.8 (observed
    # 1.0 on CPU), exact EOS retirement parity on an eos-bearing request.
    from paddle_tpu.models.generation import paged_pool_block_bytes
    if backend == "tpu":
        cap_n, cap_plen, cap_out, cap_slots, cap_fp_blocks = 16, 32, 16, 16, 17
    else:
        cap_n, cap_plen, cap_out, cap_slots, cap_fp_blocks = 8, 16, 8, 8, 10
    budget = cap_fp_blocks * paged_pool_block_bytes(cfg, blk)
    i8_blocks = budget // paged_pool_block_bytes(cfg, blk, kv_quant="int8")
    seq_blocks = -(-(cap_plen + cap_out) // blk)          # ceil
    cap_fp = (cap_fp_blocks - 1) // seq_blocks
    cap_i8 = min((i8_blocks - 1) // seq_blocks, cap_slots)
    cap_prompts = [rng.integers(0, cfg.vocab_size,
                                (cap_plen,)).astype(np.int32)
                   for _ in range(cap_n)]

    def run_capacity(kv_quant, num_blocks):
        eng = ServingEngine(params, cfg, ServingConfig(
            block_size=blk, max_slots=cap_slots, max_model_len=mlen,
            decode_chunk=chunk, queue_depth=cap_n, prefix_cache=None,
            num_blocks=num_blocks, kv_quant=kv_quant))
        rids = [eng.submit(p, max_new_tokens=cap_out, eos_token_id=None)
                for p in cap_prompts]
        peak = 0
        while eng.pending:
            # single-iteration dispatches so live concurrency is SAMPLED
            # mid-trace (a drain-the-tail dispatch would retire everything
            # between observations); peak live == blocks-limited admission
            eng.step(max_iters=1)
            peak = max(peak, eng.stats()["live_slots"])
        return eng, [eng.request(r) for r in rids], peak

    eng_cf, cap_fp_reqs, cap_fp_live = run_capacity(None, cap_fp_blocks)
    eng_c8, cap_i8_reqs, cap_i8_live = run_capacity("int8", int(i8_blocks))
    cap_len_parity = all(len(a.tokens) == len(b.tokens) for a, b in
                         zip(cap_fp_reqs, cap_i8_reqs))
    per_req_agree = [float(np.mean(np.asarray(a.output()) ==
                                   np.asarray(b.output())))
                     for a, b in zip(cap_fp_reqs, cap_i8_reqs)]
    cap_agree = float(np.mean(per_req_agree))
    # EOS parity on a request whose int8 trace matched fp exactly (greedy
    # argmax under quantization noise DOES flip occasionally — that drift
    # is the documented tolerance above; EOS retirement must be exact
    # where the streams agree): the eos id from its fp trace must retire
    # the int8 engine at the same token and length. Exactness is only
    # DEFINED where the streams agree through the eos point — if every
    # request drifted before it (possible on other backends/configs
    # within the agreement tolerance), the check is vacuous and reports
    # None rather than failing the gate on a non-regression.
    ei = int(np.argmax(per_req_agree))
    if per_req_agree[ei] == 1.0:
        eos_id = int(cap_fp_reqs[ei].tokens[cap_out // 2])
        eos_fp = eng_cf.run([cap_prompts[ei]], max_new_tokens=cap_out,
                            eos_token_id=eos_id)[0]
        eos_i8 = eng_c8.run([cap_prompts[ei]], max_new_tokens=cap_out,
                            eos_token_id=eos_id)[0]
        cap_eos_parity = bool(np.array_equal(np.asarray(eos_fp),
                                             np.asarray(eos_i8)))
    else:
        cap_eos_parity = None

    # ---- tensor-parallel row: pool sharded across the tp mesh (ISSUE 12)
    # per-chip concurrent capacity at a FIXED PER-DEVICE byte budget: a
    # TP=2 replica's devices each hold half of every token's KV (the pool
    # shards its kv-heads axis; block tables stay global), so the same
    # per-device budget backs 2x the blocks -> 2x the concurrent
    # sequences per chip at unchanged block-table logic. The row sizes a
    # TP=1 and a TP=2 pool to ONE per-device budget, serves the same
    # trace through both (greedy + a seeded-sampling wave), and asserts
    # bit-parity across mesh shapes, one decode executable per engine,
    # zero leaked blocks, and that the sharded pool actually fits the
    # per-device budget. The static >= 2x ratio is the
    # serving_tp_capacity_ratio anchor — the first row feeding the
    # MULTICHIP trajectory from the serving stack.
    tp_supported = len(jax.devices()) >= 2
    if tp_supported:
        if backend == "tpu":
            tp_n, tp_plen, tp_out, tp_slots, tp_blocks1 = 16, 32, 16, 16, 17
        else:
            tp_n, tp_plen, tp_out, tp_slots, tp_blocks1 = 8, 16, 8, 8, 10
        tp_budget = tp_blocks1 * paged_pool_block_bytes(cfg, blk)
        tp2_blocks = tp_budget // paged_pool_block_bytes(cfg, blk, tp=2)
        tp_seq_blocks = -(-(tp_plen + tp_out) // blk)          # ceil
        tp_cap1 = (tp_blocks1 - 1) // tp_seq_blocks
        tp_cap2 = min((tp2_blocks - 1) // tp_seq_blocks, tp_slots)
        tp_prompts = [rng.integers(0, cfg.vocab_size,
                                   (tp_plen,)).astype(np.int32)
                      for _ in range(tp_n)]

        def run_tp(tp, num_blocks):
            eng = ServingEngine(params, cfg, ServingConfig(
                block_size=blk, max_slots=tp_slots, max_model_len=mlen,
                decode_chunk=chunk, queue_depth=tp_n, prefix_cache=None,
                num_blocks=num_blocks, tp=tp))
            eng.run(tp_prompts[:2], max_new_tokens=2,
                    eos_token_id=None)                  # warm/compile
            t0 = time.time()
            rids = [eng.submit(p, max_new_tokens=tp_out,
                               eos_token_id=None) for p in tp_prompts]
            peak = 0
            while eng.pending:
                # single-iteration dispatches: live concurrency SAMPLED
                # mid-trace, same methodology as the int8 capacity row
                eng.step(max_iters=1)
                peak = max(peak, eng.stats()["live_slots"])
            outs = [eng.request(r).output() for r in rids]
            elapsed = time.time() - t0
            # seeded-sampling wave: identical seeds on both mesh shapes
            srids = [eng.submit(p, max_new_tokens=tp_out,
                                eos_token_id=None, temperature=0.9,
                                top_k=20, top_p=0.95, seed=i + 1)
                     for i, p in enumerate(tp_prompts[:4])]
            while eng.pending:
                eng.step()
            souts = [eng.request(r).output() for r in srids]
            return eng, outs, souts, peak, elapsed

        eng_t1, tp_o1, tp_s1, tp_live1, _ = run_tp(1, tp_blocks1)
        eng_t2, tp_o2, tp_s2, tp_live2, tp_t2 = run_tp(2, int(tp2_blocks))
        tp_match = all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(tp_o1 + tp_s1, tp_o2 + tp_s2))
        tp_leaked = eng_t1.cache.manager.blocks_in_use + \
            eng_t2.cache.manager.blocks_in_use
        tp_tok_s = tp_n * tp_out / tp_t2
        tp_ratio = tp_cap2 / max(tp_cap1, 1)
        assert tp_match, \
            "TP=2 outputs diverged from the TP=1 engine"
        assert eng_t1.stats()["decode_traces"] == 1 and \
            eng_t2.stats()["decode_traces"] == 1, "TP row recompiled decode"
        assert tp_leaked == 0, f"TP row leaked {tp_leaked} blocks"
        assert eng_t2.cache.kv_bytes(per_shard=True) <= tp_budget, \
            "TP=2 per-device pool bytes exceed the per-device budget"
        assert tp_ratio >= 2.0, \
            f"TP=2 pool backs only {tp_ratio}x concurrent sequences " \
            f"(static block arithmetic)"
        # the MEASURED half (same methodology as the int8 capacity row):
        # the 2x must show up as actually-admitted live concurrency, not
        # just block arithmetic — an admission bug keyed on the wrong
        # budget would leave the peak flat while the ratio stays 2.0
        assert tp_live2 >= 2 * tp_live1, \
            f"TP=2 peaked at {tp_live2} live vs TP=1's {tp_live1} — " \
            f"the capacity win did not materialize as admissions"

    # ---- spec-decode row: n-gram drafting + paged verify (ISSUE 11) -----
    # tok/s across an acceptance-rate sweep: a HIGH-acceptance trace
    # (self-continuation prompts — each prompt is seeded with the model's
    # own greedy stream, so the prompt-lookup drafter finds the stream's
    # cycles and the verify accepts several tokens per dispatch) vs a
    # LOW-acceptance trace (the incoherent random prompts: no n-gram
    # reoccurs, every step falls through to the plain decode loop, so the
    # only cost is the host-side lookup scan). Interleaved rounds, median
    # of per-round ratios — the same drift-immune methodology as the
    # mixed/prefix rows. In-section asserts: greedy spec output is
    # BIT-IDENTICAL to plain greedy decode on both traces (the
    # acceptance-agnostic correctness oracle), drafts were actually
    # accepted on the high trace, the verify compiled ONCE, zero blocks
    # remain in use after rollback on every engine, and the low-
    # acceptance ratio is bounded (>= 0.9x — falling through must not
    # cost real throughput). The >= 1.3x high-acceptance bound is the
    # serving_spec_speedup anchor.
    # the row runs its OWN small-vocab model: a random-init vocab-2048
    # model's greedy streams never revisit an n-gram inside a bench-sized
    # window (no trained induction behavior), so NO prompt-lookup system
    # would find drafts there — at vocab 128 greedy streams fall into
    # cycles (measured), which is the repetitive regime spec decoding
    # exists for. The seeds below were SCREENED against the simulated
    # drafter (acceptance > 0.75 over the served window); the in-section
    # acceptance assert re-verifies them on every run, so a model-init
    # change fails loudly instead of silently measuring a no-draft trace.
    from paddle_tpu.models.llama import LlamaConfig as _LC
    sp_cfg = _LC(vocab_size=128, hidden_size=256, intermediate_size=768,
                 num_hidden_layers=3, num_attention_heads=8,
                 num_key_value_heads=4, max_position_embeddings=128)
    sp_params = llama.init_params(sp_cfg, jax.random.PRNGKey(0))
    sp_seeds = [12, 17, 24, 67]
    if backend == "tpu":
        sp_pre, sp_out, sp_k, sp_slots = 32, 32, 6, 8
    else:
        sp_pre, sp_out, sp_k, sp_slots = 32, 32, 6, 4
    sp_base = [np.random.default_rng(s).integers(0, 128, (8,))
               .astype(np.int32) for s in sp_seeds]
    sp_longs = [np.asarray(G.generate(sp_params, jnp.asarray(b[None]),
                                      sp_cfg,
                                      max_new_tokens=sp_pre + sp_out))[0]
                for b in sp_base]
    sp_hi = [np.concatenate([b, l[:sp_pre]])
             for b, l in zip(sp_base, sp_longs)]
    sp_lo = [rng.integers(0, 128, (sp_pre + 8,)).astype(np.int32)
             for _ in sp_seeds]

    def mk_spec_engine(k):
        return ServingEngine(sp_params, sp_cfg, ServingConfig(
            block_size=8, max_slots=sp_slots, max_model_len=128,
            decode_chunk=chunk, queue_depth=len(sp_hi), prefix_cache=None,
            spec_decode=k, spec_ngram=2))

    def run_spec(eng, trace):
        t0 = time.time()
        outs = eng.run(trace, max_new_tokens=sp_out, eos_token_id=None)
        return outs, time.time() - t0

    eng_sp, eng_ns = mk_spec_engine(sp_k), mk_spec_engine(None)
    sp_rounds, lo_rounds = [], []
    sp_match = lo_match = True
    sp_leaked = 0
    for trace, rounds in ((sp_hi, sp_rounds), (sp_lo, lo_rounds)):
        run_spec(eng_ns, trace)                        # warm/compile
        run_spec(eng_sp, trace)                        # warm/compile
        for _ in range(5):
            o_ns, t_ns = run_spec(eng_ns, trace)
            o_sp, t_sp = run_spec(eng_sp, trace)
            rounds.append((t_ns, t_sp))
            ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(o_sp, o_ns))
            if trace is sp_hi:
                sp_match &= ok
            else:
                lo_match &= ok
            sp_leaked += eng_sp.cache.manager.blocks_in_use
            sp_leaked += eng_ns.cache.manager.blocks_in_use
    spst = eng_sp.stats()
    spec_speedup = float(np.median([a / b for a, b in sp_rounds]))
    spec_lo_ratio = float(np.median([a / b for a, b in lo_rounds]))
    spec_tok_s = len(sp_hi) * sp_out / float(np.median(
        [b for _, b in sp_rounds]))
    spec_accept_rate = (spst["spec_accepted"] / spst["spec_drafted"]
                        if spst["spec_drafted"] else 0.0)
    assert sp_match and lo_match, "spec-decode output diverged from " \
        "plain greedy decode"
    # the screened seeds must still be the high-acceptance regime —
    # a model-init change that kills the cycles fails loudly here
    assert spec_accept_rate >= 0.5, spec_accept_rate
    assert spst["spec_traces"] == 1, spst["spec_traces"]
    assert sp_leaked == 0, f"{sp_leaked} blocks leaked after rollback"
    assert spec_lo_ratio >= 0.9, \
        f"low-acceptance trace paid {spec_lo_ratio:.3f}x (bound 0.9)"
    # the 1.3x acceptance bound is the serving_spec_speedup anchor; the
    # in-section floor guards gross regressions without making tier-1
    # hostage to host-load noise (measured 1.6-1.8x median on CPU)
    assert spec_speedup >= 1.1, \
        f"high-acceptance trace only {spec_speedup:.3f}x (floor 1.1)"

    # ---- overload row: 2x-capacity arrivals, EDF vs FIFO (ISSUE 6) ------
    # the same burst of requests hits both engines; the FIFO engine is the
    # status quo (no lifecycle — every request eventually served, TTFT
    # tail = queue drain), the EDF engine gets per-request TTFT SLOs
    # (timeout_s) CALIBRATED to the measured FIFO makespan (tight classes
    # M/8..M/2 plus an always-feasible 4M class, shuffled against arrival
    # order) and SHEDS what cannot meet them. Expected shape: EDF's p99
    # TTFT over served requests collapses to roughly its (reduced)
    # makespan while FIFO's sits at the full drain, and goodput —
    # SLO-met tokens per second — is no worse, because FIFO burns its
    # slots serving requests that are already past their deadlines.
    # Outputs stay the proof: every served request must bit-match the
    # dense oracle (timed-out partials must PREFIX-match).
    if backend == "tpu":
        ov_n, ov_slots, ov_plen, ov_out = 48, 8, 32, 16
    else:
        ov_n, ov_slots, ov_plen, ov_out = 24, 4, 12, 8
    ov_prompts = [rng.integers(0, cfg.vocab_size,
                               (ov_plen,)).astype(np.int32)
                  for _ in range(ov_n)]
    ov_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(ov_prompts)), cfg, max_new_tokens=ov_out))

    from paddle_tpu.inference.serving import autoscale_signal

    def run_overload(policy, slos=None):
        eng = ServingEngine(params, cfg, ServingConfig(
            block_size=blk, max_slots=ov_slots, max_model_len=mlen,
            decode_chunk=chunk, queue_depth=ov_n, prefix_cache=None,
            policy=policy))
        eng.run(ov_prompts[:2], max_new_tokens=2, eos_token_id=None)  # warm
        t0 = time.time()
        rids = [eng.submit(
            p, max_new_tokens=ov_out, eos_token_id=None,
            timeout_s=None if slos is None else slos[i])
            for i, p in enumerate(ov_prompts)]
        # the telemetry an autoscaler consumes, read MID-BURST (ISSUE 7):
        # a 2x-capacity queue must register as a scale-up recommendation
        mid_sig = autoscale_signal(eng.health_snapshot())
        while eng.pending:
            eng.step()
        return eng, [eng.request(r) for r in rids], time.time() - t0, \
            mid_sig

    _, fifo_reqs, fifo_mk, _ = run_overload("fifo")
    slo_classes = np.tile([fifo_mk / 8, fifo_mk / 4, fifo_mk / 2,
                           4 * fifo_mk], ov_n // 4 + 1)[:ov_n]
    rng.shuffle(slo_classes)
    eng_ov, edf_reqs, edf_mk, ov_sig = run_overload("edf",
                                                    slos=slo_classes)

    def served(reqs):
        return [r for r in reqs if r.state == "finished"]

    def ov_match(reqs):
        return all((np.asarray(r.output()) ==
                    ov_oracle[i][:len(r.tokens)]).all() and
                   (r.state != "finished" or len(r.tokens) == ov_out)
                   for i, r in enumerate(reqs) if r.tokens)

    def good_tok_s(reqs, mk):
        good = sum(len(r.tokens) for i, r in enumerate(reqs)
                   if r.state == "finished" and r.ttft_s is not None
                   and r.ttft_s <= slo_classes[i])
        return good / mk

    fifo_p99 = pct([r.ttft_s for r in served(fifo_reqs)], 99)
    edf_p99 = pct([r.ttft_s for r in served(edf_reqs)], 99)
    ovst = eng_ov.stats()
    ov_shed = ovst["shed"] + ovst["timed_out"]
    fifo_good = good_tok_s(fifo_reqs, fifo_mk)
    edf_good = good_tok_s(edf_reqs, edf_mk)

    # ---- front-line row: asyncio server + supervised engine (ISSUE 7) --
    # a mini trace served THROUGH the asyncio front line (in-process
    # port-free transport, same handler the TCP/SSE path serializes) with
    # an engine crash injected mid-trace: the supervisor must rebuild
    # without recompiling (shared EnginePrograms), resubmit every
    # non-terminal request, keep every streamed output bit-identical to
    # the dense oracle, then drain clean on close() — zero leaked blocks
    from paddle_tpu.inference.serving import (EngineSupervisor,
                                              ServingServer, serve_requests)
    from paddle_tpu.testing.chaos import engine_crash
    if backend == "tpu":
        fl_n, fl_out = 8, 16
    else:
        fl_n, fl_out = 6, 8
    fl_prompts = [rng.integers(0, cfg.vocab_size,
                               (ov_plen,)).astype(np.int32)
                  for _ in range(fl_n)]
    fl_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(fl_prompts)), cfg, max_new_tokens=fl_out))
    # same shape signature as the overload engines -> reuse the compiled
    # programs (the supervisor's own restart-sharing mechanism)
    sup = EngineSupervisor(params, cfg, ServingConfig(
        block_size=blk, max_slots=ov_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=fl_n, prefix_cache=None),
        programs=eng_ov.programs)
    engine_crash(sup, at_step=3)          # fires mid-trace under the pump
    fl = serve_requests(ServingServer(sup), fl_prompts,
                        max_new_tokens=fl_out, eos_token_id=None)
    fl_s, fl_report = fl["elapsed_s"], fl["drain_report"]
    fl_match = all(np.array_equal(np.asarray(o, np.int32), fl_oracle[i])
                   for i, o in enumerate(fl["outputs"]))

    # ---- fleet row: multi-replica router + replica_kill + rolling roll --
    # (ISSUE 9) a 2-replica router (shared compiled programs — spawning
    # the fleet costs zero new compiles, trace-counter-proven) serves the
    # front-line trace with one replica KILLED mid-flight: the router
    # must fail its requests over to the survivor bit-exactly; then a
    # rolling restart across the whole fleet — which also REBUILDS the
    # killed replica — serves a second live trace with zero failures
    from paddle_tpu.inference.serving import ServingRouter
    from paddle_tpu.testing.chaos import replica_kill
    router = ServingRouter(params, cfg, ServingConfig(
        block_size=blk, max_slots=ov_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=fl_n, prefix_cache=None),
        replicas=2, programs=eng_ov.programs)
    rt_traces0 = eng_ov.programs.stats["decode_traces"]
    t0 = time.time()
    rt_frids = [router.submit(p, max_new_tokens=fl_out, eos_token_id=None)
                for p in fl_prompts]
    router.step(2)                        # progress on both replicas
    replica_kill(router, rid=router.replicas[0])
    while router.pending:
        router.step()
    rt_s = time.time() - t0
    rt_match = all(np.array_equal(router.result(f), fl_oracle[i])
                   for i, f in enumerate(rt_frids))
    rsnap = router.health_snapshot()
    rt_leaked = sum(p["in_use"]
                    for p in router.block_partitions().values())
    # rolling restart under live traffic: zero failed requests
    roll_frids = [router.submit(p, max_new_tokens=fl_out,
                                eos_token_id=None) for p in fl_prompts]
    router.start_rolling_restart()
    while router.pending or router.rolling:
        router.step(2)
    roll_match = all(np.array_equal(router.result(f), fl_oracle[i])
                     for i, f in enumerate(roll_frids))
    roll_snap = router.health_snapshot()
    rt_leaked += sum(p["in_use"]
                     for p in router.block_partitions().values())

    # ---- replay row: fleet-scale chaos replay + capacity report ---------
    # (ISSUE 13) a deterministic diurnal workload (Zipf tenants, shared-
    # prefix families, sampled rows, cancels/disconnects/abandons,
    # retrying shed clients) driven through an AUTOSCALING fleet — built
    # on the shared compiled programs, so the whole row costs zero new
    # compiles — under a seeded chaos timeline, with the InvariantAuditor
    # sampling every few steps and exhaustively at quiesce (a violation
    # RAISES, failing the section). The p99 effect is measured against
    # the honest counterfactual: the SAME manifest on a FIXED fleet —
    # step-indexed arrival->first-token latency (counts shed-retry waits)
    # and makespan must both improve under autoscaling. Emits
    # serving_replay_goodput: SLO-met tokens/s per chip.
    import dataclasses as _dc
    from paddle_tpu.inference.serving import WorkloadSpec, run_replay
    if backend == "tpu":
        rp_requests, rp_horizon, rp_queue = 400, 80, 8
    else:
        rp_requests, rp_horizon, rp_queue = 200, 56, 6
    rp_spec = WorkloadSpec(
        requests=rp_requests, seed=13, vocab_size=cfg.vocab_size,
        horizon_steps=rp_horizon, prefix_len=2 * blk,
        tail_lens=(2, 4, 6), output_lens=(2, 3, 4, 6),
        autoscale_every=8, audit_every=4)
    rp_sc = ServingConfig(block_size=blk, max_slots=ov_slots,
                          max_model_len=mlen, decode_chunk=chunk,
                          queue_depth=rp_queue)
    rp = run_replay(params, cfg, spec=rp_spec, serving_config=rp_sc,
                    replicas=2, chaos_events=4,
                    programs=eng_ov.programs)
    rp_fixed = run_replay(
        params, cfg, spec=_dc.replace(rp_spec, autoscale_every=0),
        serving_config=rp_sc, replicas=2, chaos_events=4,
        programs=eng_ov.programs)
    assert rp["violations"] == [] and rp_fixed["violations"] == [], \
        (rp["violations"], rp_fixed["violations"])
    assert rp["failed"] == 0 and rp["router_failed"] == 0, rp["outcomes"]
    assert rp["gave_up"] == 0, rp["outcomes"]
    assert rp["leaked_blocks"] == 0, rp["leaked_blocks"]
    assert rp["drain_report"]["leaked_blocks"] == 0
    assert rp["autoscale"]["spawns"] >= 1 and \
        rp["autoscale"]["drains"] >= 1, rp["autoscale"]
    assert len(rp["chaos_kinds"]) >= 2, rp["chaos_kinds"]
    # the measured autoscale effect (deterministic: step-indexed)
    assert rp["arrival_ttft_steps_p99"] < \
        rp_fixed["arrival_ttft_steps_p99"], \
        (rp["arrival_ttft_steps_p99"], rp_fixed["arrival_ttft_steps_p99"])
    assert rp["steps"] < rp_fixed["steps"], \
        (rp["steps"], rp_fixed["steps"])
    assert rp["capacity"]["sizing"], "capacity report missing"

    # ---- KV tiering row: host-RAM offload tier (ISSUE 16) ---------------
    # prefix-family re-visit trace through an UNDERSIZED device pool: the
    # families' combined working set overflows HBM, so serving them in
    # sequence churns the early families' chains out. Tier ON: refcount-0
    # evictions swap to bounded host RAM, and the re-visit wave readmits
    # the evicted chains H2D (prefix hits — checksummed, so a corrupt
    # host block degrades to a MISS, never wrong KV). Tier OFF: the same
    # re-visit re-prefills from scratch. Both engines use chunked prefill
    # so the restore path and the recompute path share one executable —
    # the TTFT ratio measures data movement vs prefill FLOPs, not a
    # compile. Parity + the swap counters are the row's proof; the
    # wall-clock ratio is the emitted metric.
    if backend == "tpu":
        tr_fam, tr_per, tr_pre, tr_tail, tr_out = 4, 3, 64, 16, 8
    else:
        tr_fam, tr_per, tr_pre, tr_tail, tr_out = 4, 2, 48, 8, 4
    tr_slots, tr_blocks, tr_host = 2, 24, 64
    tr_prefixes = [rng.integers(0, cfg.vocab_size,
                                (tr_pre,)).astype(np.int32)
                   for _ in range(tr_fam)]
    tr_prompts = [np.concatenate(
        [pre, rng.integers(0, cfg.vocab_size, (tr_tail,)).astype(np.int32)])
        for pre in tr_prefixes for _ in range(tr_per)]
    # re-visit the FIRST two families — by the end of the churn wave the
    # LRU eviction order guarantees their chains have left the device
    tr_wave2 = tr_prompts[:2 * tr_per]
    tr_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(tr_wave2)), cfg, max_new_tokens=tr_out))

    def run_tier(on):
        eng = ServingEngine(params, cfg, ServingConfig(
            block_size=blk, max_slots=tr_slots, max_model_len=pre_mlen,
            decode_chunk=chunk, queue_depth=len(tr_prompts),
            prefix_cache=True, num_blocks=tr_blocks,
            offload=on, offload_blocks=tr_host))
        eng.run(tr_prompts, max_new_tokens=tr_out,
                eos_token_id=None)                  # churn wave (+ compile)
        # warm the HIT path too: a prefix hit leaves a short residual
        # prefill that takes the chunked-prefill program — untimed here so
        # wave-2 TTFT measures steady-state restore, not a one-off compile
        eng.run([np.concatenate([tr_prefixes[-1], rng.integers(
            0, cfg.vocab_size, (tr_tail,)).astype(np.int32)])],
            max_new_tokens=tr_out, eos_token_id=None)
        st1 = eng.stats()
        t0 = time.time()
        rids = [eng.submit(p, max_new_tokens=tr_out, eos_token_id=None)
                for p in tr_wave2]
        while eng.pending:
            eng.step()
        elapsed = time.time() - t0
        reqs = [eng.request(r) for r in rids]
        st2 = eng.stats()
        hit_delta = st2["prefix_hit_tokens"] - st1["prefix_hit_tokens"]
        ttft = float(np.mean([r.ttft_s for r in reqs]))
        return eng, reqs, hit_delta, ttft, elapsed, st2

    eng_tr, tr_reqs, tr_hits_on, tr_ttft_on, tr_s_on, tr_st = run_tier(True)
    _, tr_reqs_off, tr_hits_off, tr_ttft_off, _, tr_st_off = run_tier(False)
    tr_match = all(np.array_equal(np.asarray(r.output()), tr_oracle[i])
                   for i, r in enumerate(tr_reqs)) and \
        all(np.array_equal(np.asarray(r.output()), tr_oracle[i])
            for i, r in enumerate(tr_reqs_off))
    tr_off = tr_st["offload"]
    assert tr_match, "tiering-row outputs diverged from the dense oracle"
    assert tr_off["swap_outs"] > 0, \
        "tiering row evicted nothing to the host tier"
    assert tr_off["swap_ins"] > 0 and tr_off["tier_hits"] > 0, \
        "re-visit wave never readmitted a host block"
    assert tr_off["corrupt_drops"] == 0, tr_off
    assert tr_st["recomputed_tokens"] == 0, \
        "tiering row preempted — pool too small for the slot count"
    assert tr_hits_on > tr_hits_off, \
        f"tier restored no extra prefix hits ({tr_hits_on} vs " \
        f"{tr_hits_off} without the tier)"

    # ---- migration row: scale-in drain with live KV migration (ISSUE 16)
    # the same shape signature as the overload engines -> shared compiled
    # programs, zero new compiles. One replica of a loaded 2-replica
    # fleet is drained for scale-in with migration ON: its in-flight
    # requests move (block chains + resolved decode state) to the
    # survivor and finish there bit-identically, with zero recompute,
    # zero failures and zero leaked blocks on every replica. The
    # prefill+decode tokens the survivor did NOT re-run — prompt plus
    # generated prefix per migrated request — are the recompute-saved
    # metric (under the PR 9 resubmit fallback all of it would re-run).
    from paddle_tpu.inference.serving import RouterConfig
    if backend == "tpu":
        mg_n, mg_out = 8, 24
    else:
        mg_n, mg_out = 4, 16
    mg_prompts = [rng.integers(0, cfg.vocab_size,
                               (ov_plen,)).astype(np.int32)
                  for _ in range(mg_n)]
    mg_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(mg_prompts)), cfg, max_new_tokens=mg_out))
    mg_router = ServingRouter(params, cfg, ServingConfig(
        block_size=blk, max_slots=ov_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=mg_n, prefix_cache=None),
        router_config=RouterConfig(replicas=2, migrate=True),
        programs=eng_ov.programs)
    mg_frids = [mg_router.submit(p, max_new_tokens=mg_out,
                                 eos_token_id=None) for p in mg_prompts]
    mg_router.step(1)                     # requests genuinely mid-flight
    mg_router.drain_replica(mg_router.replicas[0])
    while mg_router.pending:
        mg_router.step(1)
    mg_match = all(np.array_equal(mg_router.result(f), mg_oracle[i])
                   for i, f in enumerate(mg_frids))
    mg_snap = mg_router.health_snapshot()
    mg_recomputed = sum(rep.sup.engine.stats()["recomputed_tokens"]
                        for rep in mg_router._replicas.values())
    mg_leaked = sum(p["in_use"]
                    for p in mg_router.block_partitions().values())
    # every migrated request carries its prompt prefill + generated
    # prefix with it; the resubmit fallback recomputes all of it
    mg_saved = mg_router.migration_tokens + mg_router.migrations * ov_plen
    assert mg_match, "migrated streams diverged from the dense oracle"
    assert mg_router.migrations >= 1, \
        "scale-in drain finished without migrating anything"
    assert mg_snap["counters"]["failed"] == 0, mg_snap["counters"]
    assert mg_recomputed == 0, \
        f"migration recomputed {mg_recomputed} tokens"
    assert mg_leaked == 0, f"migration row leaked {mg_leaked} blocks"

    # ---- fleet-cache row: fleet-wide KV directory (ISSUE 17) ------------
    # the same prefix families re-visited from the WRONG replica: with
    # island caches (fleet_cache=False) each replica only ever hits what
    # it prefilled itself, so a pinned re-visit on the non-holder pays the
    # full prefill; with the fleet directory ON the router PULLS the
    # chain's blocks cross-replica (serialized on the holder, CRC-checked
    # at both ends, grafted into the target's prefix cache) and the
    # residual prefill starts depth*block_size tokens in. Placement is
    # forced with the submit() replica pin both ways, so the ONLY delta
    # between the runs is the pull. Parity, pulls >= 1, zero fallbacks
    # and zero leaks are the proofs; the re-visit TTFT ratio off/on is
    # the serving_fleet_cache_hit_ttft_ratio metric.
    fc_pre, fc_tail, fc_out = 3 * blk, max(blk // 2, 2), 4
    fc_prefixes = [rng.integers(0, cfg.vocab_size,
                                (fc_pre,)).astype(np.int32)
                   for _ in range(3)]       # fam0, fam1 + a warm family

    def fc_prompt(fam):
        return np.concatenate([fc_prefixes[fam], rng.integers(
            0, cfg.vocab_size, (fc_tail,)).astype(np.int32)])

    fc_wave2 = [fc_prompt(0), fc_prompt(1)]
    fc_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(fc_wave2)), cfg, max_new_tokens=fc_out))

    def run_fleet(on):
        rt = ServingRouter(params, cfg, ServingConfig(
            block_size=blk, max_slots=ov_slots, max_model_len=mlen,
            decode_chunk=chunk, queue_depth=8, prefix_cache=True),
            router_config=RouterConfig(replicas=2, fleet_cache=on),
            programs=eng_ov.programs)
        r0, r1 = rt.replicas[0], rt.replicas[1]
        # placement wave: fam0 -> replica 0, fam1 -> replica 1, warm -> 0
        for fam, rid in ((0, r0), (1, r1), (2, r0)):
            rt.submit(fc_prompt(fam), max_new_tokens=fc_out,
                      eos_token_id=None, replica=rid)
        while rt.pending:
            rt.step()
        # warm the pull/graft path untimed (the warm family pinned to the
        # NON-holder; with the directory off this is just a plain miss)
        rt.submit(fc_prompt(2), max_new_tokens=fc_out,
                  eos_token_id=None, replica=r1)
        while rt.pending:
            rt.step()
        hits0 = sum(rep.sup.engine.stats()["prefix_hit_tokens"]
                    for rep in rt._replicas.values())
        frids = [rt.submit(p, max_new_tokens=fc_out, eos_token_id=None,
                           replica=rid)
                 for p, rid in zip(fc_wave2, (r1, r0))]
        while rt.pending:
            rt.step()
        ttft = float(np.mean(
            [rt.request(f).first_token_t - rt.request(f).submit_t
             for f in frids]))
        hits = sum(rep.sup.engine.stats()["prefix_hit_tokens"]
                   for rep in rt._replicas.values()) - hits0
        match = all(np.array_equal(rt.result(f), fc_oracle[i])
                    for i, f in enumerate(frids))
        snap = rt.health_snapshot()
        leaked = sum(p["in_use"]
                     for p in rt.block_partitions().values())
        return match, ttft, hits, snap, leaked

    fc_match, fc_ttft_on, fc_hits_on, fc_snap, fc_leaked = run_fleet(True)
    fc_match_off, fc_ttft_off, fc_hits_off, fc_snap_off, fc_leaked_off = \
        run_fleet(False)
    assert fc_match and fc_match_off, \
        "fleet-cache row outputs diverged from the dense oracle"
    assert fc_snap["counters"]["cache_pulls"] >= 3, fc_snap["counters"]
    assert fc_snap["counters"]["pulled_blocks"] >= 3 * 3, \
        fc_snap["counters"]
    assert fc_snap["counters"]["pull_fallbacks"] == 0, fc_snap["counters"]
    assert fc_snap_off["counters"]["cache_pulls"] == 0, \
        "island baseline pulled — fleet_cache=False must disable pulls"
    assert fc_hits_on > fc_hits_off, \
        f"fleet pulls restored no extra prefix hits ({fc_hits_on} vs " \
        f"{fc_hits_off} on island caches)"
    assert fc_snap["counters"]["failed"] == 0 and \
        fc_snap_off["counters"]["failed"] == 0
    assert fc_leaked == 0 and fc_leaked_off == 0, \
        (fc_leaked, fc_leaked_off)

    # ---- disaggregation row: prefill-isolated decode (ISSUE 17) ---------
    # a chat stream (short prompts, all decode) sharing a fleet with long
    # prompts, at EQUAL chip count: unified = 2 decode replicas where
    # P2C lands long chunked prefills next to chat decodes; disagg = 1
    # decode + 1 prefill replica where long prompts prefill on the
    # dedicated pool and hand their finished chain to the decode replica
    # via the adopt path (recomputed_tokens == 0). Chat inter-token gaps
    # are timestamped per router step; the p99 TPOT ratio unified/disagg
    # is the serving_disagg_tpot_ratio metric. Parity, handoffs >= 1,
    # zero recompute / failed / leaks are the proofs.
    if backend == "tpu":
        dg_nlong, dg_plen, dg_thresh, dg_out, dg_lout = 2, 128, 64, 16, 4
    else:
        # lout >= 4: the prefill-completing step emits TWO tokens (the
        # chunk's first token + one decode iteration), so a shorter
        # budget retires on the prefill replica before _handoffs runs
        dg_nlong, dg_plen, dg_thresh, dg_out, dg_lout = 2, 48, 32, 8, 4
    # one decode slot stays free so a finished prefill has somewhere to
    # land the moment it hands off (a full decode replica is the
    # legitimate fallback path — decode in place — but the row wants the
    # handoff exercised, not just the collapse)
    dg_chat = ov_slots - 1
    dg_chat_prompts = [rng.integers(0, cfg.vocab_size,
                                    (ov_plen,)).astype(np.int32)
                       for _ in range(dg_chat)]
    dg_long_prompts = [rng.integers(0, cfg.vocab_size,
                                    (dg_plen,)).astype(np.int32)
                      for _ in range(dg_nlong)]
    dg_chat_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(dg_chat_prompts)), cfg, max_new_tokens=dg_out))
    dg_long_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(dg_long_prompts)), cfg, max_new_tokens=dg_lout))

    def run_disagg(disagg):
        rc = (RouterConfig(replicas=1, prefill_replicas=1,
                           prefill_len_threshold=dg_thresh)
              if disagg else RouterConfig(replicas=2))
        # chunked prefill ON (prefill_chunk): the whole point of the row
        # is long prefills advancing chunk-by-chunk — in the unified
        # fleet those chunks land between chat decode iterations (the
        # TPOT contention being measured); a whole-prompt prefill would
        # also finish tiny long requests inside one step, before the
        # handoff could move them
        rt = ServingRouter(params, cfg, ServingConfig(
            block_size=blk, max_slots=ov_slots, max_model_len=mlen,
            decode_chunk=chunk, prefill_chunk=2 * blk,
            queue_depth=dg_chat + dg_nlong, prefix_cache=None),
            router_config=rc, programs=eng_ov.programs)
        # untimed warm drain: one request of each class end to end (the
        # disagg pass takes the prefill-route + handoff path here)
        rt.submit(dg_long_prompts[0], max_new_tokens=dg_lout,
                  eos_token_id=None)
        rt.submit(dg_chat_prompts[0], max_new_tokens=dg_out,
                  eos_token_id=None)
        while rt.pending:
            rt.step(1)
        lf = [rt.submit(p, max_new_tokens=dg_lout, eos_token_id=None)
              for p in dg_long_prompts]
        cf = [rt.submit(p, max_new_tokens=dg_out, eos_token_id=None)
              for p in dg_chat_prompts]
        last, gaps = {}, []
        while rt.pending:
            emitted = rt.step(1)
            now = time.time()
            for f in cf:
                for _tok in emitted.get(f, ()):
                    if f in last:
                        gaps.append(now - last[f])
                    last[f] = now
        match = all(np.array_equal(rt.result(f), dg_long_oracle[i])
                    for i, f in enumerate(lf)) and \
            all(np.array_equal(rt.result(f), dg_chat_oracle[i])
                for i, f in enumerate(cf))
        snap = rt.health_snapshot()
        recomputed = sum(rep.sup.engine.stats()["recomputed_tokens"]
                         for rep in rt._replicas.values())
        leaked = sum(p["in_use"]
                     for p in rt.block_partitions().values())
        return match, pct(gaps, 99), snap, recomputed, leaked

    dg_match, dg_p99_dis, dg_snap, dg_recomputed, dg_leaked = \
        run_disagg(True)
    dg_match_uni, dg_p99_uni, dg_snap_uni, _, dg_leaked_uni = \
        run_disagg(False)
    assert dg_match and dg_match_uni, \
        "disaggregation row outputs diverged from the dense oracle"
    assert dg_snap["counters"]["prefill_routed"] >= 1, dg_snap["counters"]
    assert dg_snap["counters"]["prefill_handoffs"] >= 1, \
        "disagg row never handed a finished prefill to a decode replica"
    assert dg_recomputed == 0, \
        f"disagg handoff recomputed {dg_recomputed} tokens"
    assert dg_snap["counters"]["failed"] == 0 and \
        dg_snap_uni["counters"]["failed"] == 0
    assert dg_leaked == 0 and dg_leaked_uni == 0, \
        (dg_leaked, dg_leaked_uni)

    # ---- durability row: crash-safe journal + cold-restart recovery -----
    # (ISSUE 18) two halves. OVERHEAD: the headline mixed trace served
    # with the request journal OFF vs ON (per-step fsync'd WAL appends),
    # interleaved rounds sharing the headline engine's compiled programs,
    # min-of-rounds per side — the journal must cost < 5% (asserted).
    # RECOVERY: a journaled supervisor serving the front-line trace is
    # KILLED without grace mid-flight (``process_kill``: the userspace
    # WAL tail dies, only fsynced state survives — no drain, no final
    # snapshot) and a NEW supervisor is rebuilt via
    # ``EngineSupervisor.recover(journal_dir)`` — the timed cold start is
    # the serving_recovery_ms metric. Every pre-kill delivered stream +
    # its post-recovery remainder must equal the dense oracle exactly:
    # zero lost requests, zero re-delivered tokens, both asserted here.
    import tempfile as _tf
    from paddle_tpu.inference.serving import RequestJournal
    from paddle_tpu.testing.chaos import process_kill

    dj_sc = ServingConfig(block_size=blk, max_slots=max_slots,
                          max_model_len=mlen, decode_chunk=chunk,
                          queue_depth=n_req, prefix_cache=None)

    def dj_round(j):
        eng = ServingEngine(params, cfg, dj_sc,
                            programs=engine.programs, journal=j)
        t0 = time.time()
        for p, o in zip(prompts, outs):
            eng.submit(p, max_new_tokens=int(o), eos_token_id=None)
        while eng.pending:
            eng.step()
        return time.time() - t0

    dj_round(None)                                      # warm
    dj_round(RequestJournal(_tf.mkdtemp(prefix="bj-w")))
    dj_off, dj_on = [], []
    # 4 interleaved rounds per side: min-of-2 still reads a host-load
    # spike as journal cost on the 1-core box (observed 5.4% on a run
    # that measured -8% an hour earlier); min-of-4 is stable
    for _ in range(4):
        dj_off.append(dj_round(None))
        dj_on.append(dj_round(RequestJournal(_tf.mkdtemp(prefix="bj-"))))
    dj_overhead = (min(dj_on) - min(dj_off)) / min(dj_off) * 100.0
    assert dj_overhead < 5.0, \
        f"journal overhead {dj_overhead:.2f}% >= 5% on the mixed trace"

    dj_dir = _tf.mkdtemp(prefix="bj-kill-")
    dj_sup = EngineSupervisor(params, cfg, ServingConfig(
        block_size=blk, max_slots=ov_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=fl_n, prefix_cache=None),
        programs=eng_ov.programs, journal=RequestJournal(dj_dir))
    dj_ids = [dj_sup.submit(p, max_new_tokens=fl_out, eos_token_id=None)
              for p in fl_prompts]
    dj_pre = {s: [] for s in dj_ids}
    for _ in range(3):                # kill mid-flight, between steps
        for s, toks in dj_sup.step(max_iters=1).items():
            dj_pre[s].extend(int(t) for t in toks)
    dj_jid = {s: dj_sup._reqs[s].jid for s in dj_ids}
    dj_kill = process_kill(dj_sup)    # the fleet object is dead now
    del dj_sup
    t0 = time.time()
    dj_rec = EngineSupervisor.recover(
        dj_dir, params, cfg, serving_config=ServingConfig(
            block_size=blk, max_slots=ov_slots, max_model_len=mlen,
            decode_chunk=chunk, queue_depth=fl_n, prefix_cache=None),
        programs=eng_ov.programs)
    dj_recovery_ms = (time.time() - t0) * 1e3
    dj_by_jid = {rec.jid: srid for srid, rec in dj_rec._reqs.items()}
    dj_post = {s: [] for s in dj_ids}
    while any(not rec.terminal for rec in dj_rec._reqs.values()):
        emitted = dj_rec.step()
        for srid, toks in emitted.items():
            jid = dj_rec._reqs[srid].jid
            orig = next(s for s in dj_ids if dj_jid[s] == jid)
            dj_post[orig].extend(int(t) for t in toks)
    dj_lost = dj_dup = 0
    dj_match = True
    for i, s in enumerate(dj_ids):
        want = [int(t) for t in fl_oracle[i]]
        got = dj_pre[s] + dj_post[s]
        # got == want proves both halves at once: nothing lost (every
        # oracle token delivered exactly once across the kill) and
        # nothing duplicated (recovery never re-emitted a pre-kill token)
        if got != want:
            dj_match = False
        if dj_jid[s] not in dj_by_jid or len(got) < len(want):
            dj_lost += 1              # request dropped or stream cut short
        dj_dup += max(0, len(got) - len(want))
    assert dj_match and dj_lost == 0 and dj_dup == 0, \
        (dj_match, dj_lost, dj_dup)
    dj_leaked = dj_rec.engine.cache.manager.blocks_in_use
    assert dj_leaked == 0, f"{dj_leaked} blocks leaked after recovery"

    # ---- multi-adapter LoRA row (ISSUE 19) ------------------------------
    # the headline mixed trace served round-robin across 8 LoRA adapters
    # from ONE paged pool vs the base-only engine — same interleaved
    # min-of-rounds methodology as the durability row. The pool's cost is
    # the gathered batched adapter matmul riding the shared decode
    # program, so the bound is < 10% (asserted). Three proofs ride along:
    # zero-adapter traffic through the pool is bit-identical to the dense
    # oracle, the 8-adapter mix adds ZERO decode executables (per-slot
    # adapter ids are a device operand, not a trace key), and the pool
    # leaks no KV blocks.
    from paddle_tpu.models.lora import lora_init_params

    lr_rank, lr_adapters = 4, 8
    lr_eng = ServingEngine(params, cfg, ServingConfig(
        block_size=blk, max_slots=max_slots, max_model_len=mlen,
        decode_chunk=chunk, queue_depth=n_req, prefix_cache=None,
        lora_rank=lr_rank, lora_slots=lr_adapters, lora_pool=lr_adapters))
    for i in range(lr_adapters):
        lr_eng.register_adapter(
            f"lora{i}", lora_init_params(cfg, lr_rank, seed=i, scale=0.5))
    lr_ids = [f"lora{i % lr_adapters}" for i in range(n_req)]

    def lr_round(eng, ids):
        t0 = time.time()
        rids = [eng.submit(p, max_new_tokens=int(o), eos_token_id=None,
                           adapter_id=a)
                for p, o, a in zip(prompts, outs, ids)]
        while eng.pending:
            eng.step()
        outs_ = [np.asarray(eng.request(r).output()) for r in rids]
        return outs_, time.time() - t0

    lr_base_out, _ = lr_round(lr_eng, [None] * n_req)     # warm + parity
    lr_match = all((a == np.asarray(s)).all()
                   for a, s in zip(lr_base_out, static_out))
    lr_round(lr_eng, lr_ids)                              # adapters resident
    lr_traces0 = lr_eng.stats()["decode_traces"]
    lr_off, lr_on = [], []
    for _ in range(4):
        lr_off.append(lr_round(engine, [None] * n_req)[1])
        lr_on.append(lr_round(lr_eng, lr_ids)[1])
    lr_overhead = (min(lr_on) - min(lr_off)) / min(lr_off) * 100.0
    assert lr_overhead < 10.0, \
        f"adapter overhead {lr_overhead:.2f}% >= 10% on the mixed trace"
    lr_st = lr_eng.stats()
    assert lr_st["decode_traces"] == lr_traces0, \
        "adapter round-robin recompiled the decode program"
    lr_leaked = lr_eng.cache.manager.blocks_in_use
    assert lr_leaked == 0, f"{lr_leaked} blocks leaked by the LoRA row"

    # ---- mixed-batching row (ISSUE 20): chunked prefill fused into the
    # decode dispatch. A long-prompt + decode-heavy trace: chat requests
    # decode while long prompts stream in and chunk through prefill. The
    # two-phase engine pays each mid-prefill prompt's B=1 chunk dispatch
    # BEFORE the decode dispatch every step — with TWO longs chunking
    # concurrently that is 3 dispatches per step, and _limit clamps the
    # decode burst at decode_chunk while they prefill, so every chat
    # token behind the burst waits out the whole stalled step. The mixed
    # engine folds the chunks into the decode dispatch as extra query
    # rows — ONE dispatch per step, a token every step. Both engines
    # driven at step(decode_chunk) — the two-phase engine's own
    # production pacing (the clamp makes anything larger equivalent),
    # and a cap the mixed engine only meets AFTER the stall clears, so
    # post-stall pacing is identical on both sides. Interleaved rounds,
    # the chat TPOT p99 ratio (unmixed/mixed) is the tracked metric.
    # Parity (mixed streams bit-equal to the two-phase oracle AND the
    # dense oracle), reduced dispatches-per-step, compile-once (flat
    # decode/mixed trace counters across role churn) and zero leaks are
    # all asserted.
    mx_chat_n, mx_long_n = max_slots - 2, 2
    mx_chat_plen, mx_chat_out = blk, 24      # <= chunk: fast-path admit
    mx_long_plen, mx_long_out = 10 * blk, 2  # chunks through 10 dispatches
    mx_chat_prompts = [rng.integers(0, cfg.vocab_size,
                                    (mx_chat_plen,)).astype(np.int32)
                       for _ in range(mx_chat_n)]
    mx_long_prompts = [rng.integers(0, cfg.vocab_size,
                                    (mx_long_plen,)).astype(np.int32)
                      for _ in range(mx_long_n)]
    mx_chat_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(mx_chat_prompts)), cfg, max_new_tokens=mx_chat_out))
    mx_long_oracle = np.asarray(G.generate(params, jnp.asarray(
        np.stack(mx_long_prompts)), cfg, max_new_tokens=mx_long_out))

    def mk_mixed(mixed):
        return ServingEngine(params, cfg, ServingConfig(
            block_size=blk, max_slots=max_slots, max_model_len=mlen,
            decode_chunk=chunk, prefill_chunk=blk,
            queue_depth=mx_chat_n + mx_long_n, prefix_cache=None,
            mixed_batch=mixed), programs=engine.programs)

    def mx_round(eng):
        cf = [eng.submit(p, max_new_tokens=mx_chat_out, eos_token_id=None)
              for p in mx_chat_prompts]
        lf = [eng.submit(p, max_new_tokens=mx_long_out, eos_token_id=None)
              for p in mx_long_prompts]
        eng.step(1)                           # admission: everyone seated
        st0 = eng.stats()
        last, gaps = {}, []
        while eng.pending:
            emitted = eng.step(chunk)
            now = time.time()
            for f in cf:
                for _tok in emitted.get(f, ()):
                    if f in last:
                        gaps.append(now - last[f])
                    last[f] = now
        st1 = eng.stats()
        streams = [np.asarray(eng.request(r).output()) for r in cf + lf]
        disp = (st1["chunks"] - st0["chunks"]) / \
            max(st1["steps"] - st0["steps"], 1)
        return streams, pct(gaps, 99), disp

    mx_on, mx_off = mk_mixed(True), mk_mixed(False)
    mx_round(mx_on)                                   # warm/compile
    mx_round(mx_off)
    mx_traces0 = (mx_on.stats()["mixed_traces"],
                  mx_on.stats()["decode_traces"])
    mx_match, mx_rounds = True, []
    for _ in range(4):
        s_on, p99_on, disp_on = mx_round(mx_on)
        s_off, p99_off, disp_off = mx_round(mx_off)
        mx_match &= all(np.array_equal(a, b)
                        for a, b in zip(s_on, s_off))
        mx_match &= all(
            np.array_equal(s_on[i], mx_chat_oracle[i])
            for i in range(mx_chat_n)) and all(
            np.array_equal(s_on[mx_chat_n + i], mx_long_oracle[i])
            for i in range(mx_long_n))
        mx_rounds.append((p99_on, p99_off, disp_on, disp_off))
    mx_p99_on = float(np.median([r[0] for r in mx_rounds]))
    mx_p99_off = float(np.median([r[1] for r in mx_rounds]))
    mx_tpot_ratio = float(np.median([r[1] / max(r[0], 1e-9)
                                     for r in mx_rounds]))
    mx_disp_on = float(np.median([r[2] for r in mx_rounds]))
    mx_disp_off = float(np.median([r[3] for r in mx_rounds]))
    assert mx_match, \
        "mixed-batching row diverged from the two-phase/dense oracle"
    assert mx_tpot_ratio > 1.0, \
        f"mixed batching did not beat two-phase chat TPOT p99 " \
        f"({mx_tpot_ratio:.3f}x)"
    assert mx_disp_on < mx_disp_off, \
        f"mixed batching did not reduce dispatches/step " \
        f"({mx_disp_on:.2f} vs {mx_disp_off:.2f})"
    mx_st = mx_on.stats()
    assert (mx_st["mixed_traces"], mx_st["decode_traces"]) == mx_traces0 \
        and mx_st["mixed_traces"] == 1, \
        "mixed row retraced across admission churn"
    mx_leaked = mx_on.cache.manager.blocks_in_use + \
        mx_off.cache.manager.blocks_in_use
    assert mx_leaked == 0, f"{mx_leaked} blocks leaked by the mixed row"

    return {
        "serving_tok_s": round(serving_tok_s, 1),
        "static_tok_s": round(static_tok_s, 1),
        "speedup": round(speedup, 3),
        "outputs_match": bool(match),
        "recompiles_constant": st["decode_traces"] == traces_before,
        "decode_traces": st["decode_traces"],
        "prefill_buckets": st["prefill_buckets"],
        "chunks": st["chunks"],
        "ttft_p50_ms": pct(serve_ttft, 50),
        "ttft_p99_ms": pct(serve_ttft, 99),
        "static_ttft_p50_ms": pct(static_ttft, 50),
        "static_ttft_p99_ms": pct(static_ttft, 99),
        "tok_lat_p50_ms": pct(serve_lat, 50) if serve_lat else None,
        "tok_lat_p99_ms": pct(serve_lat, 99) if serve_lat else None,
        "requests": n_req, "max_slots": max_slots,
        "total_new_tokens": total_tokens,
        "kv_pool_mb": st["kv_pool_mb"],
        # shared-prefix row (acceptance bound: >= 1.3x vs no-prefix-cache)
        "prefix_speedup": round(prefix_speedup, 3),
        "prefix_tok_s": round(prefix_tok_s, 1),
        "prefix_outputs_match": bool(pre_match),
        "prefix_hit_tokens": pst["prefix_hit_tokens"],
        "prefix_cached_blocks": pst["cached_blocks"],
        # preemption-pressure row (proof: parity + at least 1 preemption)
        "preempt_outputs_match": bool(pp_match),
        "preemptions": ppst["preemptions"],
        "recomputed_tokens": ppst["recomputed_tokens"],
        "preempt_decode_traces": ppst["decode_traces"],
        "oom_truncated": ppst["oom_truncated"],
        # long-context row (ISSUE 10): flash-decoding kernel vs gather —
        # tok/s per context length per path, token-exact across paths,
        # ONE decode executable per engine
        **lc_rows,
        "longctx_outputs_match": bool(lc_match),
        "longctx_recompiles_constant": bool(lc_traces_ok),
        # KV capacity row (ISSUE 10): int8 vs fp pool at one byte budget
        "kv_budget_bytes": int(budget),
        "kv_fp_blocks": int(cap_fp_blocks - 1),
        "kv_int8_blocks": int(i8_blocks - 1),
        "kv_fp_concurrent": int(cap_fp),
        "kv_int8_concurrent": int(cap_i8),
        "kv_capacity_ratio": round(cap_i8 / max(cap_fp, 1), 2),
        "kv_fp_peak_live": int(cap_fp_live),
        "kv_int8_peak_live": int(cap_i8_live),
        "kv_fp_preemptions": eng_cf.stats()["preemptions"],
        "kv_int8_preemptions": eng_c8.stats()["preemptions"],
        "kv_length_parity": bool(cap_len_parity),
        "kv_token_agreement": round(cap_agree, 4),
        "kv_eos_parity": bool(cap_eos_parity),
        "kv_int8_pool_bytes": eng_c8.cache.kv_bytes(),
        # tensor-parallel row (ISSUE 12): the paged pool sharded on its
        # kv-heads axis over the tp mesh — per-chip concurrent capacity
        # at one fixed per-device byte budget, bit-parity across mesh
        # shapes asserted in-section (absent only on single-device
        # platforms, where no mesh can be built)
        "tp_supported": bool(tp_supported),
        **({"tp_degree": 2,
            "tp_per_device_budget_bytes": int(tp_budget),
            "tp1_blocks": int(tp_blocks1 - 1),
            "tp2_blocks": int(tp2_blocks - 1),
            "tp1_concurrent": int(tp_cap1),
            "tp2_concurrent": int(tp_cap2),
            "tp_capacity_ratio": round(tp_ratio, 2),
            "tp1_peak_live": int(tp_live1),
            "tp2_peak_live": int(tp_live2),
            "tp_outputs_match": bool(tp_match),
            "tp_leaked_blocks": int(tp_leaked),
            "tp_tok_s": round(tp_tok_s, 1),
            "tp2_shard_bytes": int(eng_t2.cache.kv_bytes(per_shard=True)),
            "tp_decode_traces": eng_t2.stats()["decode_traces"],
            } if tp_supported else {}),
        # spec-decode row (ISSUE 11): n-gram drafting + multi-query verify
        # vs the same engine without speculation — output bit-parity on
        # BOTH traces, acceptance > 0, one verify executable and zero
        # leaked blocks are asserted in-section; the high-acceptance
        # speedup is the serving_spec_speedup metric (anchor/bound 1.3)
        "spec_speedup": round(spec_speedup, 3),
        "spec_low_accept_ratio": round(spec_lo_ratio, 3),
        "spec_tok_s": round(spec_tok_s, 1),
        "spec_outputs_match": bool(sp_match and lo_match),
        "spec_accept_rate": round(spec_accept_rate, 3),
        "spec_drafted": spst["spec_drafted"],
        "spec_accepted": spst["spec_accepted"],
        "spec_steps": spst["spec_steps"],
        "spec_traces": spst["spec_traces"],
        "spec_leaked_blocks": int(sp_leaked),
        # overload row (EDF + TTFT SLOs + shedding vs status-quo FIFO)
        "overload_requests": ov_n,
        # pct() already converts to ms
        "overload_fifo_p99_ttft_ms": round(fifo_p99, 2),
        "overload_edf_p99_ttft_ms": round(edf_p99, 2),
        "overload_p99_ratio": round(fifo_p99 / max(edf_p99, 1e-6), 3),
        "overload_fifo_goodput_tok_s": round(fifo_good, 1),
        "overload_edf_goodput_tok_s": round(edf_good, 1),
        "overload_shed": int(ov_shed),
        "overload_served": len(served(edf_reqs)),
        "overload_outputs_match": bool(ov_match(fifo_reqs) and
                                       ov_match(edf_reqs)),
        "overload_edf_decode_traces": ovst["decode_traces"],
        # autoscale telemetry read mid-burst (ISSUE 7 acceptance: the
        # overload burst must register as a scale-up recommendation)
        "autoscale_action": ov_sig["action"],
        "autoscale_queue_pressure": ov_sig["queue_pressure"],
        # front-line row (ISSUE 7): crash-under-server recovery proof
        "frontline_requests": fl_n,
        "frontline_outputs_match": bool(fl_match),
        "frontline_restarts": sup.restarts,
        "frontline_resubmitted": sup.resubmitted,
        "frontline_tok_s": round(fl_n * fl_out / fl_s, 1),
        "frontline_drain_completed": fl_report["completed"]
        if fl_report else None,
        "frontline_leaked_blocks": fl_report["leaked_blocks"]
        if fl_report else None,
        # fleet row (ISSUE 9): replica_kill failover + rolling restart
        "router_replicas": 2,
        "router_outputs_match": bool(rt_match),
        "router_failovers": rsnap["counters"]["failovers"],
        # failed is a lifetime counter: the roll-phase snapshot already
        # folds in any kill-phase failures
        "router_failed": roll_snap["counters"]["failed"],
        "router_leaked_blocks": int(rt_leaked),
        "router_tok_s": round(fl_n * fl_out / rt_s, 1),
        "router_roll_outputs_match": bool(roll_match),
        "router_roll_restarts": roll_snap["counters"]["replica_restarts"],
        "router_decode_traces":
            eng_ov.programs.stats["decode_traces"],
        "router_recompiles_constant":
            eng_ov.programs.stats["decode_traces"] == rt_traces0,
        # replay row (ISSUE 13): fleet-scale chaos replay + capacity
        # report — zero violations / failed==0 / autoscale actuation /
        # the p99-vs-fixed-fleet effect are asserted in-section above;
        # the detail record pins the run so the row can't silently
        # vanish, and serving_replay_goodput is the tracked metric
        "replay_requests": rp["requests"],
        "replay_completed": rp["completed"],
        "replay_outcomes": rp["outcomes"],
        "replay_failed": rp["failed"],
        "replay_gave_up": rp["gave_up"],
        "replay_retries": rp["retries"],
        "replay_shed_submits": rp["shed_submits"],
        "replay_violations": len(rp["violations"]),
        "replay_leaked_blocks": rp["leaked_blocks"],
        "replay_chaos_kinds": rp["chaos_kinds"],
        "replay_chaos_firings": len(rp["chaos_fired"]),
        "replay_steps": rp["steps"],
        "replay_elapsed_s": rp["elapsed_s"],
        "replay_autoscale_spawns": rp["autoscale"]["spawns"],
        "replay_autoscale_drains": rp["autoscale"]["drains"],
        "replay_mean_fleet": rp["mean_fleet"],
        "replay_arrival_ttft_p99_steps": rp["arrival_ttft_steps_p99"],
        "replay_fixed_arrival_ttft_p99_steps":
            rp_fixed["arrival_ttft_steps_p99"],
        "replay_fixed_steps": rp_fixed["steps"],
        "replay_ttft_p50_ms": (round(rp["ttft_s_p50"] * 1e3, 2)
                               if rp["ttft_s_p50"] is not None else None),
        "replay_ttft_p99_ms": (round(rp["ttft_s_p99"] * 1e3, 2)
                               if rp["ttft_s_p99"] is not None else None),
        "replay_goodput_tok_s": rp["goodput_tok_s"],
        "replay_goodput_tok_s_per_chip": rp["goodput_tok_s_per_chip"],
        "replay_capacity_sizing": rp["capacity"]["sizing"],
        "replay_manifest_crc": rp["manifest"].tag.split("crc=")[-1],
        # KV tiering row (ISSUE 16): host-RAM offload tier under an
        # undersized device pool — parity, swap counters, zero recompute
        # and the extra prefix hits are asserted in-section; the re-visit
        # TTFT ratio (off/on) is the serving_tier_hit_ttft_ratio metric
        "tier_outputs_match": bool(tr_match),
        "tier_hit_ttft_ratio": round(tr_ttft_off / max(tr_ttft_on, 1e-9),
                                     3),
        "tier_ttft_on_ms": round(tr_ttft_on * 1e3, 2),
        "tier_ttft_off_ms": round(tr_ttft_off * 1e3, 2),
        "tier_revisit_s": round(tr_s_on, 3),
        "tier_swap_outs": tr_off["swap_outs"],
        "tier_swap_ins": tr_off["swap_ins"],
        "tier_hits": tr_off["tier_hits"],
        "tier_misses": tr_off["tier_misses"],
        "tier_corrupt_drops": tr_off["corrupt_drops"],
        "tier_host_blocks": tr_off["blocks"],
        "tier_host_capacity": tr_off["capacity"],
        "tier_prefix_hit_tokens": int(tr_hits_on),
        "tier_off_prefix_hit_tokens": int(tr_hits_off),
        "tier_recomputed_tokens": tr_st["recomputed_tokens"],
        # migration row (ISSUE 16): scale-in drain with live KV migration
        # — parity, migrations >= 1, zero failed/recompute/leaks asserted
        # in-section; recompute-saved is the tracked metric
        "migration_outputs_match": bool(mg_match),
        "migrations": int(mg_router.migrations),
        "migration_tokens": int(mg_router.migration_tokens),
        "migration_fallbacks": int(mg_router.migration_fallbacks),
        "migration_recompute_saved": int(mg_saved),
        "migration_failed": mg_snap["counters"]["failed"],
        "migration_recomputed_tokens": int(mg_recomputed),
        "migration_leaked_blocks": int(mg_leaked),
        # fleet-cache row (ISSUE 17): cross-replica pulls through the
        # fleet directory vs island caches — parity, pulls, zero
        # fallbacks/leaks asserted in-section; the pinned re-visit TTFT
        # ratio (off/on) is the tracked metric
        "fleet_outputs_match": bool(fc_match and fc_match_off),
        "fleet_hit_ttft_ratio": round(fc_ttft_off / max(fc_ttft_on, 1e-9),
                                      3),
        "fleet_ttft_on_ms": round(fc_ttft_on * 1e3, 2),
        "fleet_ttft_off_ms": round(fc_ttft_off * 1e3, 2),
        "fleet_cache_pulls": fc_snap["counters"]["cache_pulls"],
        "fleet_pulled_blocks": fc_snap["counters"]["pulled_blocks"],
        "fleet_pull_fallbacks": fc_snap["counters"]["pull_fallbacks"],
        "fleet_directory_hits": fc_snap["counters"]["directory_hits"],
        "fleet_prefix_hit_tokens": int(fc_hits_on),
        "fleet_island_hit_tokens": int(fc_hits_off),
        "fleet_directory_entries": fc_snap["directory"]["entries"],
        "fleet_leaked_blocks": int(fc_leaked + fc_leaked_off),
        # disaggregation row (ISSUE 17): chat-decode p99 TPOT at equal
        # chip count, unified vs prefill-isolated — parity, handoffs,
        # zero recompute/failed/leaks asserted in-section; the p99 TPOT
        # ratio (unified/disagg) is the tracked metric
        "disagg_outputs_match": bool(dg_match and dg_match_uni),
        "disagg_tpot_ratio": round(dg_p99_uni / max(dg_p99_dis, 1e-9), 3),
        "disagg_chat_tpot_p99_ms": dg_p99_dis,
        "unified_chat_tpot_p99_ms": dg_p99_uni,
        "disagg_prefill_routed": dg_snap["counters"]["prefill_routed"],
        "disagg_prefill_handoffs":
            dg_snap["counters"]["prefill_handoffs"],
        "disagg_handoff_fallbacks":
            dg_snap["counters"]["handoff_fallbacks"],
        "disagg_recomputed_tokens": int(dg_recomputed),
        "disagg_failed": dg_snap["counters"]["failed"],
        "disagg_leaked_blocks": int(dg_leaked + dg_leaked_uni),
        # durability row (ISSUE 18): journal overhead < 5%, kill -9
        # mid-trace + timed cold-restart recovery with zero lost
        # requests and zero re-delivered tokens — all asserted
        # in-section; serving_recovery_ms is the tracked metric
        "durable_outputs_match": bool(dj_match),
        "durable_lost_requests": int(dj_lost),
        "durable_duplicated_tokens": int(dj_dup),
        "durable_journal_overhead_pct": round(dj_overhead, 2),
        "durable_recovery_ms": round(dj_recovery_ms, 2),
        "durable_resubmitted": int(dj_rec.resubmitted),
        "durable_recovered_records": len(dj_by_jid),
        "durable_wal_bytes": int(dj_kill["wal_bytes"]),
        "durable_leaked_blocks": int(dj_leaked),
        # multi-adapter LoRA row (ISSUE 19): 8 adapters round-robin vs
        # base-only — overhead < 10%, zero-adapter bit parity, zero new
        # executables, zero leaked blocks, all asserted in-section
        "lora_outputs_match": bool(lr_match),
        "lora_adapter_overhead_pct": round(lr_overhead, 2),
        "lora_adapters": int(lr_adapters),
        "lora_decode_traces": int(lr_st["decode_traces"]),
        "lora_adapter_loads": int(lr_st["lora"]["adapter_loads"]),
        "lora_leaked_blocks": int(lr_leaked),
        # mixed-batching row (ISSUE 20): chat TPOT p99 under long-prompt
        # admission, two-phase vs mixed — parity, reduced dispatches per
        # step, compile-once, zero leaks all asserted in-section; the
        # p99 TPOT ratio (unmixed/mixed) is the tracked metric
        "mixed_outputs_match": bool(mx_match),
        "mixed_tpot_p99_ratio": round(mx_tpot_ratio, 3),
        "mixed_chat_tpot_p99_ms": mx_p99_on,
        "unmixed_chat_tpot_p99_ms": mx_p99_off,
        "mixed_dispatches_per_step": round(mx_disp_on, 3),
        "unmixed_dispatches_per_step": round(mx_disp_off, 3),
        "mixed_traces": int(mx_st["mixed_traces"]),
        "mixed_recompiles_constant":
            (mx_st["mixed_traces"], mx_st["decode_traces"]) == mx_traces0,
        "mixed_leaked_blocks": int(mx_leaked),
    }


# recorded values — regression anchors for vs_baseline on the secondary
# rows (BASELINE.md; the headline's anchor is the 50% north star). The two
# kernel microbenches are anchored at round 3 because the timing methodology
# changed there (in-graph fori_loop instead of dispatch pipelining, which
# let per-dispatch overhead pollute the round-2 numbers).
_R2_ANCHORS = {
    "llama_wide_train_mfu": 55.1,     # % (round 2)
    "flash_attn_speedup": 1.0,        # COLOR ONLY: the composed-SDPA ref
    # executable varies 1.0-1.75x run to run (XLA autotuning); the tracked
    # kernel metric is flash_attn_ms below (r5: VERDICT r4 weak #4)
    "flash_attn_ms": 11.7,            # ms fwd+bwd causal S=2048 B4 H16 D64,
    # median of 3 genuinely-distinct executables (11.3-15.6 spread — the
    # median absorbs the occasional bad-autotune executable), DCE-proof
    # (recorded r5; an earlier 10.7 reading predated the salt fix that
    # actually diversifies the executables)
    "resnet50_throughput": 964.0,     # img/s (round 2)
    "bert_base_throughput": 605.0,    # ex/s (round 2)
    "sdxl_attn_64x64": 12.0,          # ms, lower is better. RE-ANCHORED r5
    # from the r3 value of 10.5 with a measured cause (VERDICT r4 next #2):
    # (a) r3's loop consumed only the q-grad, so XLA DCE'd the entire dkv
    # backward kernel -> 10.5 under-measured the true fwd+bwd; (b) the r4
    # driver artifact (14.46) additionally hit a frozen-bad executable in
    # the persistent compile cache. Median-of-3 FRESH executables measures
    # 11.34-11.63 for the full DCE-proof fwd+bwd; protocol now immune to
    # both effects (_median_fresh).
    # round-4 anchors for the new metrics (first recorded round)
    "llama_decode_tok_s_b8": 2500.0,  # tok/s (r4; 2000-2530 observed)
    "llama_decode_int8_tok_s_b8": 2500.0,  # tok/s (first recorded r5:
    # weight-only-int8 decode via quantize_params + the Pallas stream-
    # dequant kernel; anchored at the fp16 rate until measured)
    "ppyoloe_mbv3_throughput": 400.0,  # img/s (r4)
    "llama_train_mfu_tuned": 56.4,    # % (r4)
    # fault-tolerance cost rows (first recorded this round; lower is
    # better for both). The overhead anchor IS the acceptance bound from
    # the robustness issue (<15% step overhead while a save is in flight);
    # restore-verify anchored provisionally until measured on the driver.
    "ckpt_async_overhead_pct": 15.0,   # % step-time overhead bound
    "ckpt_restore_verify_ms": 500.0,   # ms, provisional anchor
    # perf-layer rows (first recorded this round). resnet_nhwc shares the
    # NCHW row's r2 anchor on purpose: its vs_baseline directly reads as
    # the layout win against the 0.523-regressed NCHW number.
    "resnet_nhwc_throughput": 964.0,   # img/s, anchored to the NCHW row
    "input_overlap_pct": 50.0,         # % of H2D hidden, provisional
    "input_h2d_ms_per_batch": 10.0,    # ms, lower is better, provisional
    # run-health sentinel row (first recorded this round; lower is
    # better). The anchor IS the acceptance bound from the robustness
    # issue: <= 2% step overhead for the fused NaN/Inf/spike detector on
    # the tuned llama row.
    "health_sentinel_overhead_pct": 2.0,
    # serving rows (first recorded this round). The speedup anchor IS the
    # acceptance bound from the serving issue: continuous batching over
    # the paged KV cache must beat arrival-order static batching >= 1.5x
    # in aggregate tok/s on the mixed-length trace. The absolute tok/s
    # anchor is provisional until measured on the driver.
    "serving_throughput_speedup": 1.5,
    "serving_agg_tok_s": 3000.0,
    # the shared-prefix serving row's anchor IS its acceptance bound (r6):
    # prefix-cache engine vs the same engine with the cache off, median of
    # interleaved per-round ratios
    "serving_prefix_speedup": 1.3,
    # overload row (ISSUE 6): FIFO-p99-TTFT / EDF-p99-TTFT under
    # 2x-capacity arrivals — the anchor IS the acceptance bound (EDF must
    # beat FIFO, ratio > 1; the in-section assert enforces it)
    "serving_overload_p99_ratio": 1.0,
    # fleet row (ISSUE 9): aggregate tok/s through the 2-replica router
    # while one replica is killed mid-trace and failover recomputes its
    # in-flight work — provisional until measured on the driver (the
    # row's real proofs — bit-parity, failovers >= 1, zero leaks, a
    # zero-failure rolling restart — are asserted in-section)
    "serving_router_tok_s": 60.0,      # tok/s observed on CPU incl. the
    #                                    kill + failover recompute window
    # KV capacity row (ISSUE 10): concurrent sequences the int8 pool
    # admits vs the fp pool at ONE byte budget — the anchor IS the
    # acceptance bound (>= 2x; arithmetic gives ~3.5x for fp32 pools and
    # the in-section assert enforces the 2x floor)
    "serving_kv_capacity_ratio": 2.0,
    # TP capacity anchor IS the acceptance bound (r12): per-chip
    # concurrent sequences at a fixed per-device byte budget, TP=2 vs
    # TP=1 — the kv-heads split is exact, so the static ratio is 2.0 by
    # construction and any regression is a sharding-layout bug
    "serving_tp_capacity_ratio": 2.0,
    # spec-decode row (ISSUE 11): tok/s with n-gram drafting + multi-
    # query verify vs the same engine without speculation on the
    # high-acceptance (self-continuation) trace — the anchor IS the
    # acceptance bound (>= 1.3x; the low-acceptance trace's >= 0.9x
    # fall-through bound and output bit-parity are asserted in-section)
    "serving_spec_speedup": 1.3,
    # replay row (ISSUE 13): SLO-met tokens per second per chip through
    # the autoscaling fleet under the seeded chaos timeline — the
    # goodput-per-chip number the next perf PRs move (the row's real
    # proofs — zero violations, failed==0, autoscale actuated with a
    # measured p99 effect vs the fixed-fleet counterfactual — are
    # asserted in-section). Anchored at the CPU measurement.
    "serving_replay_goodput": 19.0,    # tok/s/chip observed on CPU
    # KV tiering row (ISSUE 16): re-visit TTFT with the host offload
    # tier OFF over ON — re-visit TTFT with the tier off (full re-prefill)
    # over tier on (H2D restore + residual prefill). On CPU the bench
    # model is so small that ONE fused re-prefill dispatch beats ~12
    # per-block restore dispatches, so the steady-state CPU ratio sits
    # well below 1; it is tracked because dispatch-path regressions (e.g.
    # per-block-index recompiles) tank it by an order of magnitude. The
    # >= 1.0 payoff claim belongs to real accelerators + real model
    # sizes, where re-prefill costs FLOPs the restore doesn't. The row's
    # hard proofs — parity, swap counters, zero recompute, extra prefix
    # hits — are asserted in-section.
    "serving_tier_hit_ttft_ratio": 0.2,  # observed CPU steady state
    # migration row (ISSUE 16): prefill+decode tokens a scale-in drain
    # did NOT recompute because live KV migration moved the chains
    # instead of resubmitting — anchored at the CPU measurement
    "serving_migration_recompute_saved": 28.0,  # tok observed on CPU
    # fleet-cache row (ISSUE 17): pinned re-visit TTFT on the NON-holder
    # replica with island caches (full re-prefill) over the fleet
    # directory (cross-replica pull + residual prefill). Same CPU caveat
    # as the tiering row: per-block D2H/H2D round trips vs ONE fused
    # re-prefill dispatch on a tiny model keeps the CPU ratio well below
    # 1 (observed 0.07-0.13); the >= 1.0 payoff belongs to real
    # accelerators where prefill costs FLOPs the pull doesn't. Tracked
    # because dispatch-path regressions (per-block-index recompiles)
    # tank it by an order of magnitude.
    "serving_fleet_cache_hit_ttft_ratio": 0.1,  # observed CPU value
    # disaggregation row (ISSUE 17): chat-decode p99 TPOT unified over
    # prefill-isolated at equal chip count. On CPU both "replicas" share
    # ONE host and router.step() runs them serially, so moving prefill
    # chunks to a dedicated replica cannot shorten wall-clock steps —
    # the observed CPU ratio sits below 1 and the >= 1.0 isolation win
    # belongs to real multi-chip fleets where replicas step
    # concurrently. The row's hard proofs (parity, handoffs >= 1,
    # recomputed_tokens == 0, zero failed/leaks) are asserted; the
    # ratio is emitted-not-asserted, like goodput.
    "serving_disagg_tpot_ratio": 0.6,  # observed CPU value
    # durability row (ISSUE 18): timed cold-restart recovery — journal
    # load (newest snapshot + WAL suffix) + supervisor rebuild on shared
    # compiled programs + bit-exact resubmission of every non-terminal
    # request. Lower is better (the emit inverts the ratio). The row's
    # hard proofs (parity across the kill, zero lost, zero duplicated,
    # journal overhead < 5%) are asserted, not tracked.
    "serving_recovery_ms": 2.0,  # observed CPU value (1.3-1.6ms: journal
    # load + supervisor rebuild are host-side and the shared compiled
    # programs make the engine build free; the resubmitted prefill
    # recompute lands in the post-recovery steps, not here)
    # multi-adapter LoRA row (ISSUE 19): the anchor is the 10% acceptance
    # bound on the gathered-adapter-matmul overhead (lower is better, the
    # emit inverts), plus the adapter population one pool serves. The
    # row's hard proofs (zero-adapter bit parity, decode_traces flat
    # across the 8-adapter round-robin, zero leaked blocks) are asserted,
    # not tracked.
    "serving_lora_adapter_overhead_pct": 10.0,
    "serving_lora_adapters_per_replica": 8,
    # mixed-batching row (ISSUE 20): chat-decode p99 TPOT two-phase over
    # mixed while long prompts chunk through prefill. The two-phase
    # engine pays each long prompt's B=1 chunk dispatch before the decode
    # dispatch every step; the mixed engine runs ONE fused dispatch, so
    # the per-token stall a streaming chat client feels shrinks by
    # roughly the extra dispatch overheads. Strictly > 1.0 is asserted
    # in-section (with parity, reduced dispatches/step, compile-once and
    # zero leaks); the anchor is the ISSUE 20 target.
    "serving_mixed_tpot_p99_ratio": 1.3,
    # dispatches per engine step on the mixed side of the same trace —
    # the steady state the tentpole promises is ONE mixed dispatch per
    # step (lower is better, the emit inverts)
    "serving_mixed_dispatches_per_step": 1.0,
}


def _emit(metric, value, unit, vs_baseline):
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      "vs_baseline": round(vs_baseline, 3)}))
    sys.stdout.flush()


def _llama_point(backend, peak, steps, wide, batch_arg=None, seq_arg=None):
    from paddle_tpu.models.llama import num_params
    cfg, batch, seq = _presets(backend, wide=wide)
    batch = batch_arg or batch
    seq = seq_arg or seq
    r = bench_train(cfg, batch, seq, steps)
    flops = _train_flops_per_step(cfg, batch, seq)
    tflops_s = flops / r["step_time_s"] / 1e12
    mfu = 100.0 * tflops_s / peak
    detail = {
        "preset": "llama_wide" if wide else "llama_ratio",
        "params": num_params(cfg), "batch": batch, "seq": seq,
        "step_time_s": round(r["step_time_s"], 4),
        "compile_s": round(r["compile_s"], 1),
        "tokens_per_s": round(r["tokens_per_s"]),
        "achieved_tflops_s": round(tflops_s, 1),
        "peak_tflops_s": peak, "mfu_pct": round(mfu, 2),
        "loss": round(r["loss"], 3),
    }
    print(json.dumps(detail), file=sys.stderr)
    return mfu


def main():
    ap = argparse.ArgumentParser()
    _SECTIONS = ("llama", "wide", "attn", "resnet", "resnet_nhwc", "bert",
                 "sdxl", "decode", "int8", "serve",
                 "tuned", "detect", "checkpoint", "input", "health",
                 "roofline")
    for sec in _SECTIONS:
        ap.add_argument(f"--{sec}", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()
    chosen = [s for s in _SECTIONS if getattr(args, s)]
    run_all = not chosen

    def want(s):
        return run_all or s in chosen

    import os
    # the serve section's tensor-parallel row (ISSUE 12) shards over >= 2
    # devices; on the CPU/host platform that means the virtual device
    # count must be raised BEFORE jax initializes its backend (the flag
    # only affects the host platform — inert on real TPU slices, where
    # the device count is the hardware's)
    if want("serve") and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
    import jax
    # Persistent compilation cache: recompiles are warm across sections AND
    # across runs, which is what keeps the whole sweep inside the 420s
    # budget — ResNet alone costs ~42s cold. Caveat (measured): the cache
    # FREEZES executable quality; XLA's compile-time autotuning varies run
    # to run (resnet step 28-38ms across fresh compiles, and one bad
    # compile cached at 61ms), so the cache is re-warmed from a
    # verified-good run rather than from whatever ran first.
    from paddle_tpu.core.place import cpu_requested
    from paddle_tpu.jit import enable_compile_cache
    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    dev = jax.devices()[0]
    if backend != "tpu" and not cpu_requested():
        # off the chip the same metric names are filled from a toy preset
        # (_presets): that is the harness smoke tier-1 runs, and it runs
        # only when the caller said so — a run that was not told to use
        # the CPU and merely found no chip must not print numbers
        sys.exit(f"bench.py: jax resolved to backend {backend!r} "
                 f"({dev.device_kind!r} x{jax.device_count()}) and "
                 f"JAX_PLATFORMS=cpu was not set; no accelerator found")
    peak = _peak_tflops(dev)
    print(json.dumps({"backend": backend, "device_kind": dev.device_kind,
                      "device_count": jax.device_count(),
                      "compile_cache_dir": cache_dir}),
          file=sys.stderr)

    t_start = time.time()
    # the self-imposed budget must expire BEFORE any plausible external
    # timeout so the final headline re-emit always runs (sections are
    # skipped, never the closing line); raise via BENCH_BUDGET_S
    budget = float(os.environ.get("BENCH_BUDGET_S", "420"))

    # rough worst-case cost per section, used to RESERVE budget: a section
    # only starts if it can plausibly finish inside the budget (round 3
    # lesson: a section that starts at 419s runs unbounded and the driver's
    # kill lands mid-section). Two tiers: cold XLA compiles vs warm
    # persistent-cache hits (the eager state-discovery warmups in
    # resnet/bert are dispatch-bound and never cached, so warm != free).
    try:
        _warm = len(os.listdir(cache_dir)) > 20
    except OSError:
        _warm = False
    _est_cost = ({"bert": 90.0, "resnet": 150.0, "resnet_nhwc": 150.0,
                  "wide": 40.0, "attn": 30.0,
                  "sdxl": 25.0, "decode": 45.0, "tuned": 35.0, "int8": 45.0,
                  "detect": 150.0, "checkpoint": 30.0,
                  "input": 20.0, "health": 45.0, "serve": 260.0} if _warm else
                 {"bert": 280.0, "resnet": 260.0, "resnet_nhwc": 260.0,
                  "wide": 90.0, "attn": 60.0,
                  "sdxl": 45.0, "decode": 90.0, "tuned": 60.0,
                  "int8": 90.0, "detect": 240.0, "checkpoint": 50.0,
                  "input": 30.0, "health": 90.0, "serve": 410.0})
    print(json.dumps({"compile_cache": "warm" if _warm else "cold"}),
          file=sys.stderr)

    failed = []

    def section(name, fn, budget_exempt=False):
        """Failure isolation + time budget: one broken or slow section must
        not hide the rest (or starve the headline). Returns fn()'s value or
        None on failure/skip; a section that RAISED is remembered, and the
        run exits non-zero after the last section."""
        elapsed = time.time() - t_start
        if not budget_exempt and elapsed + _est_cost.get(name, 60.0) > budget:
            print(json.dumps({"section": name, "elapsed_s": round(elapsed, 1),
                              "skipped": f"budget {budget}s would be "
                              "exceeded"}), file=sys.stderr)
            return None
        try:
            r = fn()
        except Exception as e:
            traceback.print_exc()
            print(json.dumps({"section": name, "error": f"{type(e).__name__}:"
                              f" {str(e)[:300]}"}), file=sys.stderr)
            failed.append(name)
            return None
        print(json.dumps({"section": name, "took_s":
                          round(time.time() - t_start - elapsed, 1)}),
              file=sys.stderr)
        return r

    # the HEADLINE runs FIRST (it must exist even if the driver kills a slow
    # secondary section; budget-exempt) and is re-emitted as the final line
    # (the driver parses the last metric line)
    headline = None

    def emit_headline():
        # a failed headline prints no value: the exit code carries it
        if headline is not None:
            _emit("llama_train_mfu", round(headline, 2), "%", headline / 50.0)

    if want("llama"):
        headline = section(
            "llama",
            lambda: _llama_point(backend, peak, args.steps, wide=False,
                                 batch_arg=args.batch, seq_arg=args.seq),
            budget_exempt=True)
        emit_headline()

        # if an EXTERNAL timeout kills us mid-section (SIGTERM), the last
        # metric line on stdout must still be the headline, not whatever
        # secondary happened to emit before the kill
        import signal

        def _on_term(signum, frame):
            emit_headline()
            sys.stdout.flush()
            os._exit(124)

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, OSError):
            pass

    # Section order = information-per-second: BERT first among secondaries
    # (round 3 lost its number to the budget), then the cheap kernel
    # microbenches, then the two big-compile sections (wide, resnet) that the
    # persistent cache makes warm.
    if want("bert"):
        def _bert():
            bt = bench_bert(steps=args.steps)
            print(json.dumps({"bert_step_s": round(bt["step_time_s"], 4),
                              "bert_compile_s": round(bt["compile_s"], 1)}),
                  file=sys.stderr)
            v = bt["examples_per_s"]
            _emit("bert_base_throughput", round(v), "ex/s",
                  v / _R2_ANCHORS["bert_base_throughput"])
        section("bert", _bert)
    if want("attn"):
        def _attn():
            a = bench_attention(steps=args.steps)
            sp = a["ref"] / a["flash"]
            print(json.dumps({"attn_flash_s": round(a["flash"], 4),
                              "attn_ref_s": round(a["ref"], 4),
                              "attn_flash_all_s": [round(t, 4) for t in
                                                   a["flash_all"]],
                              "attn_ref_all_s": [round(t, 4) for t in
                                                 a["ref_all"]]}),
                  file=sys.stderr)
            # TRACKED metric: the kernel's absolute time, median-of-fresh
            # (stable); the speedup vs the composed ref is COLOR ONLY —
            # the ref side's executable quality varies 1.0-1.75x run to
            # run (r4 VERDICT weak #4)
            _emit("flash_attn_ms", round(a["flash"] * 1e3, 2), "ms",
                  _R2_ANCHORS["flash_attn_ms"] / (a["flash"] * 1e3))
            _emit("flash_attn_speedup", round(sp, 2), "x",
                  sp / _R2_ANCHORS["flash_attn_speedup"])
        section("attn", _attn)
    if want("sdxl"):
        def _sdxl():
            s = bench_sdxl_attention(steps=args.steps)
            print(json.dumps(s), file=sys.stderr)
            v = s["sdxl_64x64_ms"]
            _emit("sdxl_attn_64x64", v, "ms",
                  _R2_ANCHORS["sdxl_attn_64x64"] / v)  # lower is better
        section("sdxl", _sdxl)
    if want("detect"):
        def _detect():
            dt = bench_detect(steps=args.steps)
            print(json.dumps({"detect_step_s": round(dt["step_time_s"], 4),
                              "detect_compile_s": round(dt["compile_s"], 1),
                              "loss": round(dt["loss"], 3)}), file=sys.stderr)
            _emit("ppyoloe_mbv3_throughput", round(dt["images_per_s"], 1),
                  "img/s", dt["images_per_s"] /
                  _R2_ANCHORS["ppyoloe_mbv3_throughput"])
        section("detect", _detect)
    if want("input"):
        def _input():
            r = bench_input(backend)
            print(json.dumps({"input": r}), file=sys.stderr)
            _emit("input_h2d_ms_per_batch", r["h2d_ms_per_batch"], "ms",
                  _R2_ANCHORS["input_h2d_ms_per_batch"] /
                  max(r["h2d_ms_per_batch"], 1e-3))   # lower is better
            _emit("input_overlap_pct", r["overlap_pct"], "%",
                  r["overlap_pct"] / _R2_ANCHORS["input_overlap_pct"])
        section("input", _input)
    if want("checkpoint"):
        def _ckpt():
            c = bench_checkpoint(backend, steps=args.steps)
            print(json.dumps({"checkpoint": c}), file=sys.stderr)
            # both rows: LOWER is better -> vs_baseline = anchor / value
            # (clamped so a near-zero overhead doesn't explode the ratio)
            v = c["overhead_pct"]
            _emit("ckpt_async_overhead_pct", v, "%",
                  _R2_ANCHORS["ckpt_async_overhead_pct"] / max(v, 1.0))
            r = c["restore_verify_ms"]
            _emit("ckpt_restore_verify_ms", r, "ms",
                  _R2_ANCHORS["ckpt_restore_verify_ms"] / max(r, 1.0))
        section("checkpoint", _ckpt)
    if want("health"):
        def _health():
            h = bench_health(backend, peak, steps=args.steps)
            print(json.dumps({"health": h}), file=sys.stderr)
            # LOWER is better; the anchor is the 2% acceptance bound.
            # Clamp: overhead can measure ~0 (or negative, timing noise)
            # and the ratio must not explode.
            v = h["overhead_pct"]
            _emit("health_sentinel_overhead_pct", v, "%",
                  _R2_ANCHORS["health_sentinel_overhead_pct"] /
                  max(v, 0.25))
        section("health", _health)
    if "roofline" in chosen:   # explicit-only: a diagnostic, not a metric
        def _roof():
            r = bench_roofline(backend, steps=args.steps)
            print(json.dumps(r), file=sys.stderr)
        section("roofline", _roof, budget_exempt=True)
    if want("tuned"):
        def _tuned():
            m, st = bench_tuned(backend, peak, steps=args.steps)
            print(json.dumps({"tuned_step_s": round(st, 4),
                              "tuned_mfu": round(m, 2)}), file=sys.stderr)
            _emit("llama_train_mfu_tuned", round(m, 2), "%",
                  m / _R2_ANCHORS["llama_train_mfu_tuned"])
        section("tuned", _tuned)
    if want("decode"):
        def _decode():
            d = bench_decode(backend)
            print(json.dumps(d), file=sys.stderr)
            _emit("llama_decode_tok_s_b8", d["decode_b8_tok_s"], "tok/s",
                  d["decode_b8_tok_s"] / _R2_ANCHORS["llama_decode_tok_s_b8"])
        section("decode", _decode)
    if want("int8"):
        def _int8():
            d = bench_decode(backend, batches=(8,), int8=True)
            print(json.dumps({"int8_" + k: v for k, v in d.items()}),
                  file=sys.stderr)
            _emit("llama_decode_int8_tok_s_b8", d["decode_b8_tok_s"],
                  "tok/s", d["decode_b8_tok_s"] /
                  _R2_ANCHORS["llama_decode_int8_tok_s_b8"])
        section("int8", _int8)
    if want("serve"):
        def _serve():
            s = bench_serve(backend)
            print(json.dumps({"serve": s}), file=sys.stderr)
            assert s["prefix_outputs_match"], \
                "prefix-cache outputs diverged from the dense oracle"
            assert s["preempt_outputs_match"], \
                "post-preemption outputs diverged from the dense oracle"
            assert s["preemptions"] >= 1, \
                "pressure row finished without exercising preemption"
            # acceptance proofs ride in the metric run itself: paged greedy
            # must match the dense static path bit-for-bit and the decode
            # executable count must not grow across the trace
            assert s["outputs_match"], "paged decode diverged from dense"
            assert s["recompiles_constant"], \
                f"decode recompiled mid-trace ({s['decode_traces']})"
            # long-context row (ISSUE 10): the Pallas flash-decoding
            # kernel must emit token streams bit-equal to the gather
            # fallback at every context length, and each path's decode
            # program must compile exactly once
            assert s["longctx_outputs_match"], \
                "paged-attention kernel diverged from the gather path"
            assert s["longctx_recompiles_constant"], \
                "long-context row recompiled decode mid-trace"
            # KV capacity row (ISSUE 10 acceptance): at one byte budget
            # the int8 pool must admit >= 2x the concurrent sequences,
            # with exact length/EOS parity and token agreement on the
            # served trace
            assert s["kv_capacity_ratio"] >= 2.0, \
                f"int8 pool admitted only {s['kv_capacity_ratio']}x " \
                f"the fp pool's concurrent sequences"
            assert s["kv_length_parity"], \
                "int8 KV trace lengths diverged from fp"
            # None = vacuous (no fully-agreeing request to define exact
            # EOS parity on — still within the agreement tolerance)
            assert s["kv_eos_parity"] is not False, \
                "int8 KV EOS retirement diverged from fp"
            assert s["kv_token_agreement"] >= 0.6, \
                f"int8 KV token agreement {s['kv_token_agreement']} " \
                f"below the 0.6 tolerance"
            # tensor-parallel row (ISSUE 12): at one per-device byte
            # budget a TP=2 replica must hold >= 2x the concurrent
            # sequences of the TP=1 engine, serve bit-identically
            # (greedy + seeded sampling), compile decode once per mesh
            # shape and leak nothing (skipped only where no second
            # device exists to build a mesh over)
            if s["tp_supported"]:
                assert s["tp_outputs_match"], \
                    "TP=2 outputs diverged from the TP=1 engine"
                assert s["tp_capacity_ratio"] >= 2.0, \
                    f"TP=2 held only {s['tp_capacity_ratio']}x " \
                    f"concurrent sequences at the per-device budget"
                assert s["tp_decode_traces"] == 1, \
                    "TP row recompiled decode mid-trace"
                assert s["tp_leaked_blocks"] == 0, \
                    f"TP row leaked {s['tp_leaked_blocks']} KV blocks"
            # overload row (ISSUE 6): every served request bit-matches the
            # oracle (timed-out partials prefix-match), load genuinely
            # shed, and the SLO-aware policy beats status-quo FIFO on p99
            # TTFT without giving up goodput
            assert s["overload_outputs_match"], \
                "overload-row outputs diverged from the dense oracle"
            assert s["overload_shed"] > 0, \
                "overload row shed nothing — not actually overloaded"
            assert s["overload_edf_p99_ttft_ms"] < \
                s["overload_fifo_p99_ttft_ms"], \
                "EDF did not beat FIFO on p99 TTFT under overload"
            # front-line row (ISSUE 7): an engine crash under the asyncio
            # server must recover bit-exactly (supervisor rebuild +
            # resubmit), drain clean, and the overload burst must read as
            # a scale-up to the autoscale hook
            assert s["frontline_outputs_match"], \
                "front-line streams diverged from the dense oracle"
            assert s["frontline_restarts"] >= 1, \
                "front-line row finished without exercising the crash " \
                "barrier"
            assert s["frontline_leaked_blocks"] == 0, \
                f"drain leaked {s['frontline_leaked_blocks']} KV blocks"
            assert s["autoscale_action"] == "scale_up", \
                f"overload burst read as {s['autoscale_action']}, " \
                f"not scale_up"
            # fleet row (ISSUE 9): a replica killed mid-trace must fail
            # over bit-exactly with no leaked blocks on ANY replica, and
            # a rolling restart must serve a live trace with zero failed
            # requests — all without a single new compile
            assert s["router_outputs_match"], \
                "router failover outputs diverged from the dense oracle"
            assert s["router_failovers"] >= 1, \
                "fleet row finished without exercising failover"
            assert s["router_failed"] == 0, \
                f"fleet row failed {s['router_failed']} request(s)"
            assert s["router_leaked_blocks"] == 0, \
                f"fleet row leaked {s['router_leaked_blocks']} KV blocks"
            assert s["router_roll_outputs_match"], \
                "rolling-restart outputs diverged from the dense oracle"
            assert s["router_roll_restarts"] >= s["router_replicas"], \
                "rolling restart did not rebuild every replica"
            assert s["router_recompiles_constant"], \
                "the fleet recompiled (programs must be shared)"
            # replay row (ISSUE 13): the in-section asserts already
            # enforce zero violations / failed==0 / autoscale actuation
            # with a measured p99 effect / zero leaks; re-pin the detail
            # record here so the row cannot silently vanish
            assert s["replay_violations"] == 0
            assert s["replay_failed"] == 0 and s["replay_gave_up"] == 0
            assert s["replay_leaked_blocks"] == 0
            assert s["replay_autoscale_spawns"] >= 1
            assert s["replay_autoscale_drains"] >= 1
            assert len(s["replay_chaos_kinds"]) >= 2
            assert s["replay_capacity_sizing"]
            # goodput ("no worse" is the row's other half) is EMITTED but
            # not asserted: the EDF pass's shed volume tracks wall-clock
            # vs the FIFO-calibrated SLOs, so on a loaded CI host EDF
            # sheds extra and wall-clock goodput swings either way
            # (observed 0.75-1.55x); the quiet-machine driver round reads
            # overload_*_goodput_tok_s. The p99 half IS structural
            # (served => TTFT <= its SLO; FIFO's tail ~= the drain) and
            # stays asserted.
            _emit("serving_agg_tok_s", s["serving_tok_s"], "tok/s",
                  s["serving_tok_s"] / _R2_ANCHORS["serving_agg_tok_s"])
            _emit("serving_throughput_speedup", s["speedup"], "x",
                  s["speedup"] / _R2_ANCHORS["serving_throughput_speedup"])
            _emit("serving_prefix_speedup", s["prefix_speedup"], "x",
                  s["prefix_speedup"] / _R2_ANCHORS["serving_prefix_speedup"])
            _emit("serving_overload_p99_ratio", s["overload_p99_ratio"],
                  "x", s["overload_p99_ratio"] /
                  _R2_ANCHORS["serving_overload_p99_ratio"])
            _emit("serving_router_tok_s", s["router_tok_s"], "tok/s",
                  s["router_tok_s"] / _R2_ANCHORS["serving_router_tok_s"])
            _emit("serving_spec_speedup", s["spec_speedup"], "x",
                  s["spec_speedup"] / _R2_ANCHORS["serving_spec_speedup"])
            _emit("serving_kv_capacity_ratio", s["kv_capacity_ratio"],
                  "x", s["kv_capacity_ratio"] /
                  _R2_ANCHORS["serving_kv_capacity_ratio"])
            _emit("serving_replay_goodput",
                  s["replay_goodput_tok_s_per_chip"], "tok/s/chip",
                  s["replay_goodput_tok_s_per_chip"] /
                  _R2_ANCHORS["serving_replay_goodput"])
            # tiering + migration rows (ISSUE 16): the hard proofs —
            # parity, swap counters, zero recompute, migrations >= 1,
            # zero failed/leaked — are asserted inside bench_serve; the
            # two metrics are the tracked numbers
            _emit("serving_tier_hit_ttft_ratio",
                  s["tier_hit_ttft_ratio"], "x",
                  s["tier_hit_ttft_ratio"] /
                  _R2_ANCHORS["serving_tier_hit_ttft_ratio"])
            _emit("serving_migration_recompute_saved",
                  s["migration_recompute_saved"], "tok",
                  s["migration_recompute_saved"] /
                  _R2_ANCHORS["serving_migration_recompute_saved"])
            # fleet-cache + disaggregation rows (ISSUE 17): parity,
            # pulls/handoffs, zero fallbacks/recompute/failed/leaks are
            # asserted inside bench_serve; re-pin the load-bearing ones
            # here so the rows cannot silently vanish, then emit the two
            # tracked metrics
            assert s["fleet_outputs_match"], \
                "fleet-cache row outputs diverged from the dense oracle"
            assert s["fleet_cache_pulls"] >= 1
            assert s["fleet_pull_fallbacks"] == 0
            assert s["fleet_leaked_blocks"] == 0
            assert s["disagg_outputs_match"], \
                "disaggregation row outputs diverged from the oracle"
            assert s["disagg_prefill_handoffs"] >= 1
            assert s["disagg_recomputed_tokens"] == 0
            assert s["disagg_failed"] == 0
            assert s["disagg_leaked_blocks"] == 0
            _emit("serving_fleet_cache_hit_ttft_ratio",
                  s["fleet_hit_ttft_ratio"], "x",
                  s["fleet_hit_ttft_ratio"] /
                  _R2_ANCHORS["serving_fleet_cache_hit_ttft_ratio"])
            _emit("serving_disagg_tpot_ratio",
                  s["disagg_tpot_ratio"], "x",
                  s["disagg_tpot_ratio"] /
                  _R2_ANCHORS["serving_disagg_tpot_ratio"])
            if s["tp_supported"]:
                _emit("serving_tp_capacity_ratio", s["tp_capacity_ratio"],
                      "x", s["tp_capacity_ratio"] /
                      _R2_ANCHORS["serving_tp_capacity_ratio"])
            # durability row (ISSUE 18): the hard proofs — bit parity
            # across the kill, zero lost requests, zero re-delivered
            # tokens, journal overhead < 5% — are asserted inside
            # bench_serve; re-pin them here so the row cannot silently
            # vanish, then emit the timed cold-restart metric (lower is
            # better, so the ratio inverts)
            assert s["durable_outputs_match"], \
                "durability row streams diverged across the kill"
            assert s["durable_lost_requests"] == 0
            assert s["durable_duplicated_tokens"] == 0
            assert s["durable_journal_overhead_pct"] < 5.0
            _emit("serving_recovery_ms", s["durable_recovery_ms"], "ms",
                  _R2_ANCHORS["serving_recovery_ms"] /
                  max(s["durable_recovery_ms"], 1e-6))
            # multi-adapter LoRA row (ISSUE 19): zero-adapter parity,
            # compile-once across the 8-adapter round-robin, overhead
            # < 10%, zero leaks — asserted in bench_serve; re-pin them
            # here so the row cannot silently vanish, then emit the
            # overhead (lower is better, ratio inverts) and the adapter
            # population one pool serves
            assert s["lora_outputs_match"], \
                "LoRA row zero-adapter traffic diverged from the oracle"
            assert s["lora_adapter_overhead_pct"] < 10.0
            assert s["lora_leaked_blocks"] == 0
            _emit("serving_lora_adapter_overhead_pct",
                  s["lora_adapter_overhead_pct"], "%",
                  _R2_ANCHORS["serving_lora_adapter_overhead_pct"] /
                  max(s["lora_adapter_overhead_pct"], 1.0))
            _emit("serving_lora_adapters_per_replica", s["lora_adapters"],
                  "adapters", s["lora_adapters"] /
                  _R2_ANCHORS["serving_lora_adapters_per_replica"])
            # mixed-batching row (ISSUE 20): bit parity against the
            # two-phase AND dense oracles, one mixed executable across
            # role churn, zero leaks — asserted in bench_serve; re-pin
            # the load-bearing ones here so the row cannot silently
            # vanish, then emit the TPOT ratio and the dispatch density
            # (lower is better, ratio inverts)
            assert s["mixed_outputs_match"], \
                "mixed-batching row diverged from the two-phase oracle"
            assert s["mixed_tpot_p99_ratio"] > 1.0
            assert s["mixed_recompiles_constant"] and \
                s["mixed_traces"] == 1
            assert s["mixed_leaked_blocks"] == 0
            assert s["mixed_dispatches_per_step"] < \
                s["unmixed_dispatches_per_step"]
            _emit("serving_mixed_tpot_p99_ratio",
                  s["mixed_tpot_p99_ratio"], "x",
                  s["mixed_tpot_p99_ratio"] /
                  _R2_ANCHORS["serving_mixed_tpot_p99_ratio"])
            _emit("serving_mixed_dispatches_per_step",
                  s["mixed_dispatches_per_step"], "disp/step",
                  _R2_ANCHORS["serving_mixed_dispatches_per_step"] /
                  max(s["mixed_dispatches_per_step"], 1e-6))
        section("serve", _serve)
    if want("wide"):
        def _wide():
            mfu = _llama_point(backend, peak, args.steps, wide=True,
                               batch_arg=args.batch, seq_arg=args.seq)
            _emit("llama_wide_train_mfu", round(mfu, 2), "%",
                  mfu / _R2_ANCHORS["llama_wide_train_mfu"])
        section("wide", _wide)
    if want("resnet"):
        def _resnet():
            rn = bench_resnet(steps=args.steps)
            print(json.dumps({"resnet50_step_s": round(rn["step_time_s"], 4),
                              "resnet50_warmup_s": round(rn["warmup_s"], 1),
                              "resnet50_compile_s": round(rn["compile_s"], 1),
                              "loss": round(rn["loss"], 3)}), file=sys.stderr)
            v = rn["images_per_s"]
            _emit("resnet50_throughput", round(v), "img/s",
                  v / _R2_ANCHORS["resnet50_throughput"])
        section("resnet", _resnet)
    if want("resnet_nhwc"):
        def _resnet_nhwc():
            rn = bench_resnet(steps=args.steps, nhwc=True)
            print(json.dumps(
                {"resnet_nhwc_step_s": round(rn["step_time_s"], 4),
                 "resnet_nhwc_warmup_s": round(rn["warmup_s"], 1),
                 "resnet_nhwc_compile_s": round(rn["compile_s"], 1),
                 "loss": round(rn["loss"], 3)}), file=sys.stderr)
            v = rn["images_per_s"]
            _emit("resnet_nhwc_throughput", round(v), "img/s",
                  v / _R2_ANCHORS["resnet_nhwc_throughput"])
        section("resnet_nhwc", _resnet_nhwc)

    # re-emit the headline LAST: honest LLaMA-ratio config vs the 50% MFU
    # north star (the driver parses the final metric line)
    if want("llama"):
        emit_headline()
    if failed:
        sys.exit(f"bench.py: section(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
