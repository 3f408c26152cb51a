"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, no child, no network, no CPU mode. It drives both front doors
of the repo once, at the full width of the one model with a chip history
(:func:`preset`: the 738M ``llama_ratio`` model of ``BASELINE.md``), on
seeded random weights:

1. **kernels** — every Pallas kernel a default or flag-reachable path can
   dispatch, compiled natively and compared with its ``jax.numpy``
   reference, so a compile refusal names the kernel instead of surfacing
   minutes later inside an engine;
2. **trainer** — ``llama.make_train_step`` through ``jit_step`` with
   donation, the path ``examples/train_llama.py`` uses;
3. **server** — ``ServingServer(EngineSupervisor(params, cfg,
   ServingConfig()))`` with every serving default, 16 concurrent streams;
4. **server_int8** — a second, small engine with an int8 KV pool and int8
   weights;
5. **oracle** — the engines' own outputs teacher-forced through the plain
   dense ``llama.forward`` in float32: logits, not tokens;
6. **four_chip** — with four devices, the server at ``tp=4``, the trainer
   over ``dp=2 x mp=2`` and ``dryrun_multichip(4)``; otherwise a skip line.

Every stage prints one JSON line carrying the device as JAX reports it and
its compile seconds apart from its run seconds. Any failure is a traceback
and a non-zero exit. The last line of standard output is
``{"ok": true, "device": {...}}``. Numbers printed here (seconds, tokens/s)
are information about one run, never a benchmark result.

The stages are plain functions of their sizes so that
``tests/test_chip_smoke.py`` can run them at toy size on the CPU mesh.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import functools
import importlib.metadata
import json
import sys
import time


def preset():
    """``(cfg, batch, seq)`` of the 738M ``llama_ratio`` model: LLaMA-7B's
    shape ratios (I/E = 2.6875) at twelve layers."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=2048, use_kernels=True, remat=True,
        dtype=jnp.bfloat16, param_dtype=jnp.float32)
    return cfg, 8, 2048


# ---------------------------------------------------------------------------
# tolerances, stated once with their reasons
# ---------------------------------------------------------------------------
# Kernel roll-call: max |kernel - reference| over max(1, max |reference|),
# by the dtype the kernel WRITES. The reference runs in float32 under
# "highest" matmul precision. bfloat16 keeps 8 significand bits (2^-8 =
# 3.9e-3 per rounding) and the flash kernels round the softmax weights to
# bf16 before the PV / dV matmuls, so a few roundings stack; a wrong mask,
# block or head is an error of order 1.
KERNEL_TOL = {"bfloat16": 3e-2, "float32": 2e-4}

# Logit oracle: for each token an engine emitted greedily, the float32
# reference logit of that token must lie within this distance of the
# reference maximum at its position. If the engine's logits are the
# reference's plus an error e, its argmax can trail the reference maximum
# by at most 2*max|e|. With this init the logits over the 32000-word
# vocabulary have a standard deviation near 1 and a maximum near 4, so a
# wrong block, a stale tail or a dropped chunk — which decorrelates the
# hidden state from the reference — lands a token units below the
# maximum. Run on the CPU at full width through the gather path, the same
# 16 requests gave worst gaps of 0.008-0.053 for the bf16 engine (bf16
# activations through 12 layers against the float32 reference) and 0.055
# for int8 weights plus an int8 KV pool; the bounds leave about 5x and
# 10x for the chip's own rounding and for the int8 engine's wider spread.
ORACLE_TOL = {"bf16": 0.25, "int8": 0.5}


def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def emit(stage: str, **fields) -> None:
    """One JSON line for one stage, always naming the device."""
    dev = device_record()
    row = {"stage": stage, "platform": dev["platform"],
           "device_kind": dev["kind"], "device_count": dev["count"]}
    row.update(fields)
    print(json.dumps(row), flush=True)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or fetching from
    the persistent cache), summed from jax's own monitoring events — so a
    stage's compile time is measured, not inferred from a first call."""

    _EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
               "backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith(self._EVENTS):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")

    def lap(self) -> dict:
        out = {"compile_s": round(self.seconds, 2), "compiles": self.compiles}
        self.seconds, self.compiles = 0.0, 0
        return out


def timed_stage(clock: CompileClock, fn, *args, **kw):
    """Run one stage; returns (its result, its time split). The stage
    itself ends every piece of device work in a host read or
    ``block_until_ready``."""
    clock.lap()
    t0 = time.time()
    out = fn(*args, **kw)
    wall = time.time() - t0
    lap = clock.lap()
    lap["run_s"] = round(max(wall - lap["compile_s"], 0.0), 2)
    return out, lap


# ---------------------------------------------------------------------------
# stage 1: kernel roll-call
# ---------------------------------------------------------------------------

def _max_err(got, want) -> float:
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if not np.isfinite(g).all():
        return float("inf")
    return float(np.max(np.abs(g - w)) / max(1.0, float(np.max(np.abs(w)))))


def _roll_one(name, fn, ref, args) -> dict:
    """Compile ``fn`` (it must dispatch a Pallas kernel), run it, and
    compare every output leaf with ``ref`` under float32 'highest'."""
    import jax
    t0 = time.time()
    traced = jax.jit(fn).trace(*args)
    if "pallas_call" not in str(traced.jaxpr):
        raise AssertionError(f"{name}: no pallas_call was dispatched — the "
                             f"kernel gave way to another path")
    compiled = traced.lower().compile()
    compile_s = time.time() - t0
    t0 = time.time()
    got = jax.block_until_ready(compiled(*args))
    run_s = time.time() - t0
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref)(*args))
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        err, tol = _max_err(g, w), KERNEL_TOL[str(g.dtype)]
        if not err <= tol:
            raise AssertionError(f"{name}: max error {err:.3g} over the "
                                 f"{g.dtype} tolerance {tol:g}")
        worst = max(worst, err / tol)
    return {"kernel": name, "compile_s": round(compile_s, 2),
            "run_s": round(run_s, 4), "err_over_tol": round(worst, 3)}


def _paged_operands(key, M, Q, H, Hk, D, bs, W, quant, dtype):
    """Operands for one paged-attention call: ragged lengths pinned
    around block boundaries, a shuffled block table, and (fp pools) NaN
    in every position no query may attend — unowned blocks, the null
    block, owned tails past a row's window."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.generation import _kv_quantize
    N, C = M * W + 1, W * bs
    edge = [0, bs - 1, bs, bs + 1, C // 3, C // 2 + 1, C - Q - bs, C - Q]
    sl = np.array([edge[m % len(edge)] for m in range(M)], np.int32)
    dl = np.minimum(np.arange(M) * 3, Q - 1).astype(np.int32)
    dl[-1] = Q - 1
    perm = np.random.default_rng(0).permutation(np.arange(1, N)).reshape(M, W)
    need = (sl + dl) // bs + 1                       # blocks a row attends
    tbl = np.where(np.arange(W)[None, :] < need[:, None], perm, 0)
    owned = np.zeros((N, bs), bool)
    for m in range(M):
        owned[tbl[m]] |= (np.arange(C) <= sl[m] + dl[m]).reshape(W, bs)
    owned[0] = False                                 # the null block
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (M, Q, H, D), jnp.float32).astype(dtype)
    ops = {"q": q if Q > 1 else q[:, 0], "tbl": jnp.asarray(tbl, jnp.int32),
           "sl": jnp.asarray(sl), "dl": jnp.asarray(dl)}
    for name, kx in (("k", kk), ("v", kv)):
        x = jax.random.normal(kx, (N, Hk, bs, D), jnp.float32)
        if quant:
            ops[name], ops[name + "s"] = _kv_quantize(x)
        else:
            ops[name] = jnp.where(owned[:, None, :, None], x,
                                  jnp.nan).astype(dtype)
    return ops


def kernel_cases(cfg, batch, seq, serving_config):
    """``(name, kernel fn, reference fn, operand builder)`` for every
    Pallas kernel a default or flag-reachable path can dispatch, at the
    preset's shapes. The builders are jax-traceable, so a test can take
    their shapes with ``jax.eval_shape`` without materializing a pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.kernels.paged_attention import paged_attention
    from paddle_tpu.kernels.quant_matmul import (quantize_weights,
                                                 weight_only_matmul)
    from paddle_tpu.kernels.rms_norm import rms_norm
    from paddle_tpu.kernels.rope import apply_rope, rope_cos_sin
    from paddle_tpu.models.generation import _kv_gather
    from paddle_tpu.models.llama import _masked_sdpa

    # the RngBitGenerator-backed key: operand builders compile in a
    # fraction of threefry's time, on the chip and in the CPU test alike
    key = jax.random.key(0, impl="rbg")
    dt = cfg.dtype
    H, D, E = cfg.num_attention_heads, cfg.head_dim, cfg.hidden_size
    gqa = (2 * H, max(H // 4, 1))                    # one GQA shape
    cases = []

    def normal(shape, dtype=dt, k=key):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)

    # --- flash attention, forward and backward
    def attn_ref(q, k, v, seg=None):
        G = q.shape[2] // k.shape[2]
        kf = jnp.repeat(k.astype(jnp.float32), G, axis=2)
        vf = jnp.repeat(v.astype(jnp.float32), G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf)
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        if seg is not None:
            mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
        p = jax.nn.softmax(jnp.where(mask, s / np.sqrt(q.shape[-1]), -1e30),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)

    def fwd_bwd(f):
        def run(q, k, v, *rest):
            def loss(q, k, v):
                return f(q, k, v, *rest).astype(jnp.float32).sum()
            return f(q, k, v, *rest), jax.grad(loss, argnums=(0, 1, 2))(
                q, k, v)
        return run

    def qkv(B, Hq, Hkv):
        ks = jax.random.split(key, 3)
        return tuple(normal((B, seq, h, D), k=kx)
                     for h, kx in zip((Hq, Hkv, Hkv), ks))

    def flash(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=True, segment_ids=seg)

    def segments():                  # four packed sequences, ragged
        cuts = np.sort(np.random.default_rng(0).choice(
            np.arange(1, seq), 3, replace=False))
        return jnp.asarray(np.searchsorted(cuts, np.arange(seq),
                                           side="right")[None], jnp.int32)

    cases += [
        ("flash_attention causal fwd+bwd", fwd_bwd(flash), fwd_bwd(attn_ref),
         lambda: qkv(2, H, H)),
        ("flash_attention varlen fwd+bwd", fwd_bwd(flash), fwd_bwd(attn_ref),
         lambda: qkv(1, H, H) + (segments(),)),
        (f"flash_attention GQA {gqa[0]}/{gqa[1]} fwd+bwd", fwd_bwd(flash),
         fwd_bwd(attn_ref), lambda: qkv(1, *gqa)),
    ]

    # --- paged attention: {decode, multi-query} x {fp, int8 pool} x
    # {MHA, GQA}; multi-query at the mixed step's widest chunk
    sc = serving_config
    bs, M = sc.block_size, sc.max_slots
    W = -(-sc.max_model_len // bs)

    def paged(o):
        return paged_attention(
            o["q"], o["k"], o["v"], o["tbl"], o["sl"],
            draft_lens=o["dl"] if o["q"].ndim == 4 else None,
            k_scale=o.get("ks"), v_scale=o.get("vs"), layer=o.get("layer"))

    def whole(o):
        """The operands with their pool as layer 1 of a pool of two (what
        a paged program's layer scan carries); layer 0 is all NaN (an int8
        pool's scales are), which no output may show."""
        under = lambda x: jnp.stack([
            jnp.zeros_like(x) if x.dtype == jnp.int8
            else jnp.full_like(x, jnp.nan), x])
        return {**o, **{n: under(o[n]) for n in ("k", "v", "ks", "vs")
                        if n in o}, "layer": jnp.int32(1)}

    def paged_ref(o):
        multi = o["q"].ndim == 4
        q = o["q"] if multi else o["q"][:, None]
        Q, Hk = q.shape[1], o["k"].shape[-3]
        pz = {"k": o["k"], "v": o["v"]}
        if "ks" in o:
            pz.update(k_scale=o["ks"], v_scale=o["vs"])
        if "layer" not in o:                     # a pool of one layer
            pz = {n: a[None] for n, a in pz.items()}
        kk, vv = _kv_gather(pz, o.get("layer", 0), o["tbl"], M, W * bs, Hk,
                            D)
        cap = jnp.minimum(jnp.arange(Q)[None, :], o["dl"][:, None]) \
            if multi else jnp.zeros((M, 1), jnp.int32)
        mask = jnp.arange(W * bs)[None, None, :] <= \
            (o["sl"][:, None] + cap)[:, :, None]
        out = _masked_sdpa(q.astype(jnp.float32), kk.astype(jnp.float32),
                           vv.astype(jnp.float32), mask)
        out = out.astype(jnp.float32 if "ks" in o else o["k"].dtype)
        if not multi:
            return out[:, 0]
        # the kernel runs query rows q <= dl and writes zeros past them
        real = jnp.arange(Q)[None, :] <= o["dl"][:, None]
        return jnp.where(real[:, :, None, None], out, 0)

    for Hq, Hkv in ((H, cfg.kv_heads), gqa):
        for quant in (False, True):
            for Q in (1, sc.prefill_chunk or 8):
                cases.append((
                    f"paged_attention {'int8' if quant else 'fp'} pool "
                    f"{Hq}/{Hkv} heads Q={Q}", paged, paged_ref,
                    lambda Q=Q, Hq=Hq, Hkv=Hkv, quant=quant: (_paged_operands(
                        key, M, Q, Hq, Hkv, D, bs, W, quant, dt),)))
    # the form the paged programs call since ISSUE 30: every layer's pool
    # and the layer's index, the kernel's copies indexing the layer
    for quant, Q in ((False, sc.prefill_chunk or 8), (True, 1)):
        cases.append((
            f"paged_attention whole {'int8' if quant else 'fp'} pool layer "
            f"1 of 2, {gqa[0]}/{gqa[1]} heads Q={Q}", paged, paged_ref,
            lambda Q=Q, quant=quant: (whole(_paged_operands(
                key, M, Q, *gqa, D, bs, W, quant, dt)),)))

    # --- weight-only int8 matmul at a decode step's M
    cases.append((
        "quant_matmul M=8", lambda x, w, s: weight_only_matmul(x, w, s),
        lambda x, w, s: (x.astype(jnp.float32) @ (
            w.astype(jnp.float32) * s[None, :])).astype(jnp.bfloat16),
        lambda: (normal((8, E), jnp.bfloat16),) + quantize_weights(
            normal((E, E), jnp.float32) / np.sqrt(E))))

    # --- the two fused elementwise kernels (flag-reachable:
    # LlamaConfig.use_fused_norm)
    def norm_ref(x, w):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return (y * w).astype(x.dtype)

    def with_grad(f):
        return lambda x, w: (f(x, w), jax.grad(
            lambda x, w: f(x, w).astype(jnp.float32).sum(), (0, 1))(x, w))

    def rope_ref(x, cos, sin):
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        rot = jnp.concatenate([-x2, x1], axis=-1).astype(jnp.float32)
        return (x.astype(jnp.float32) * cos[None, :, None, :] +
                rot * sin[None, :, None, :]).astype(x.dtype)

    cases += [
        ("rms_norm fwd+bwd", with_grad(lambda x, w: rms_norm(x, w, 1e-6)),
         with_grad(norm_ref),
         lambda: (normal((batch, seq, E)),
                  1.0 + 0.1 * normal((E,), jnp.float32))),
        ("apply_rope", apply_rope, rope_ref,
         lambda: (normal((2, seq, H, D)),) + tuple(
             rope_cos_sin(seq, D, cfg.rope_theta))),
    ]
    return cases


def kernel_rollcall(cfg, batch, seq, serving_config, interpret=False):
    """Compile and check every kernel of :func:`kernel_cases`.
    ``interpret`` is what :func:`kernels.dispatch.interpret` must say:
    False on the chip — no kernel stage may run interpreted there."""
    from paddle_tpu.kernels import dispatch
    if dispatch.interpret() is not interpret:
        raise AssertionError(f"kernels.dispatch.interpret() is "
                             f"{dispatch.interpret()}, expected {interpret}")
    import jax
    return [_roll_one(name, fn, ref, jax.jit(build)()) for name, fn, ref,
            build in kernel_cases(cfg, batch, seq, serving_config)]


# ---------------------------------------------------------------------------
# stage 2: trainer
# ---------------------------------------------------------------------------

def trainer(clock, cfg, batch, seq, steps, seed=0, dp=1, mp=1):
    """``steps`` donated train steps on one fixed seeded batch: loss
    finite and falling, donation in effect wherever the backend supports
    it, and nothing compiled after the first step (``clock`` counts jax's
    own compile events). ``dp * mp > 1`` runs the same step GSPMD-sharded
    over a hybrid mesh (parameter shards on every device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.jit.train_step import donation_supported, jit_step
    from paddle_tpu.models import llama

    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    init_opt, step_fn = llama.make_train_step(cfg, lr=1e-4)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    under_mesh = contextlib.nullcontext
    placement = None
    if dp * mp > 1:
        from jax.sharding import NamedSharding
        from paddle_tpu.distributed.topology import HybridCommunicateGroup
        hcg = HybridCommunicateGroup(dp=dp, mp=mp,
                                     devices=jax.devices()[:dp * mp])
        # the flash kernel reads the mesh from this context and runs as a
        # per-shard region (llama._flash_attention)
        under_mesh = functools.partial(jax.set_mesh, hcg.mesh)
        params = llama.shard_params(params, hcg.mesh, cfg, mp_axis="mp")
        ids = jax.device_put(ids, NamedSharding(
            hcg.mesh, llama.batch_spec(("dp", "sharding"))))
        placement = sorted(
            {d.id for leaf in jax.tree_util.tree_leaves(params)
             for d in leaf.sharding.device_set})
        if len(placement) != dp * mp:
            raise AssertionError(f"parameter shards on devices {placement}, "
                                 f"expected {dp * mp} devices")
    opt = init_opt(params)       # laid out like params (llama._adamw_init)
    jstep = jit_step(step_fn, donate_argnums=(0, 1))

    first_leaf = jax.tree_util.tree_leaves(params)[0]
    losses, step_s, compiled = [], [], []
    for _ in range(steps):
        t0 = time.time()
        with under_mesh():
            params, opt, loss = jstep(params, opt, ids, ids)
        losses.append(float(loss))                   # host read: step done
        step_s.append(time.time() - t0)
        compiled.append(clock.compiles)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    donated = bool(first_leaf.is_deleted())
    if donated is not donation_supported():
        raise AssertionError(f"donation in effect: {donated}; backend "
                             f"supports it: {donation_supported()}")
    late = compiled[-1] - compiled[0]
    if late:
        raise AssertionError(f"{late} compilation(s) after the first step")
    out = {"params": llama.num_params(cfg), "batch": batch, "seq": seq,
           "losses": [round(x, 4) for x in losses], "donated": donated,
           "compilations_after_first_step": late,
           "smoke_step_s": round(min(step_s[1:]), 4),
           "smoke_tokens_per_s": round(batch * seq / min(step_s[1:]))}
    if placement is not None:
        out["param_shard_devices"] = placement
    return out


# ---------------------------------------------------------------------------
# stages 3-5: server, int8 server, logit oracle
# ---------------------------------------------------------------------------

def make_requests(vocab, lengths, prefix, new_tokens, seed, n=16):
    """``n`` seeded requests over four prompt lengths. Requests 0, 8, 11
    and 14 share one ``prefix``-token prefix: request 0 is admitted in the
    first wave and the other three only once a slot frees, after its
    blocks are registered — so they hit the prefix cache. Requests 4 and
    10 sample; the rest are greedy."""
    import numpy as np
    rng = np.random.default_rng(seed)
    _, b, c, d = lengths
    shared = {0: b, 8: c, 11: d, 14: b}
    head = rng.integers(0, vocab, (prefix,)).astype(np.int32)
    reqs = []
    for i in range(n):
        ln = shared.get(i, lengths[i % 4])
        prompt = rng.integers(0, vocab, (ln,)).astype(np.int32)
        if i in shared:
            prompt[:prefix] = head
        kw = {"max_new_tokens": new_tokens[i % 2], "eos_token_id": None}
        if i in (4, 10):
            kw.update(temperature=0.8, top_k=20, seed=i)
        reqs.append({"prompt": prompt, "kw": kw, "shared": i in shared})
    return reqs


def serve(params, cfg, serving_config, requests):
    """Answer ``requests`` concurrently through the asyncio front line
    over one supervised engine; returns (token streams, stats row)."""
    from paddle_tpu.inference.serving import (EngineSupervisor,
                                              InvariantAuditor,
                                              ServingServer)
    sup = EngineSupervisor(params, cfg, serving_config)
    srv = ServingServer(sup)

    async def one(req):
        toks = []
        async for ev in srv.agenerate(req["prompt"], **req["kw"]):
            if ev["type"] == "token":
                toks.append(ev["token"])
        return toks

    async def run():
        async with srv.running():
            return await asyncio.gather(*(one(r) for r in requests))

    t0 = time.time()
    streams = asyncio.run(run())
    wall = time.time() - t0
    # the crash barrier stays in place, so the FIRST exception is what a
    # broken bring-up must show — not a replica quietly rebuilt or broken
    if srv.pump_error is not None:
        raise srv.pump_error
    if sup.restarts or sup.broken:
        raise RuntimeError(f"engine crashed under the supervisor "
                           f"({sup.restarts} restart(s)): {sup.crashes[0]}")
    for req, toks in zip(requests, streams):
        want = req["kw"]["max_new_tokens"]
        if len(toks) != want or not all(0 <= t < cfg.vocab_size
                                        for t in toks):
            raise AssertionError(f"stream delivered {len(toks)} tokens "
                                 f"(wanted {want}) or left the vocabulary")
    eng = sup.engine
    st = eng.stats()
    row = {k: st[k] for k in ("paged_kernel", "decode_traces", "mixed_traces",
                              "mixed_dispatches", "prefill_traces",
                              "prefix_hit_tokens",
                              "kv_quant", "tp_degree", "kv_pool_mb")}
    row.update(restarts=sup.restarts,
               blocks_in_use=eng.cache.manager.blocks_in_use,
               audit_violations=len(InvariantAuditor().quiesce(
                   eng, collect=True)),
               requests=len(requests),
               smoke_tokens_per_s=round(sum(map(len, streams)) / wall, 1))
    # one executable per shape and none traced twice: decode has one
    # shape; the mixed step has one per power-of-two chunk bucket
    # (ServingEngine._bucket), so its trace count must equal the number
    # of executables jit holds for it
    mixed_execs = eng._jmixed._cache_size()
    checks = {
        "paged_kernel": row["paged_kernel"] is True,
        "decode_traces<=1": row["decode_traces"] <= 1,
        "mixed_traces==executables": row["mixed_traces"] == mixed_execs >= 1,
        "mixed_dispatches>=1": row["mixed_dispatches"] >= 1,
        "blocks_in_use==0": row["blocks_in_use"] == 0,
        "audit clean": row["audit_violations"] == 0,
    }
    if any(r["shared"] for r in requests):
        checks["prefix_hit_tokens>0"] = row["prefix_hit_tokens"] > 0
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise AssertionError(f"server checks failed: {failed}; {row}")
    if serving_config.tp > 1:
        row["shard_devices"] = _tp_placement(eng, serving_config.tp)
    return streams, row


def _tp_placement(eng, tp):
    """Every pool leaf and wq/wk/wv really spread over ``tp`` devices,
    each holding bytes."""
    import jax
    layers = eng._params["layers"]
    leaves = dict(eng.cache.pool, **{n: layers[n] for n in ("wq", "wk", "wv")})
    for name, leaf in leaves.items():
        devs = {s.device.id for s in leaf.addressable_shards}
        shapes = {s.data.shape for s in leaf.addressable_shards}
        if len(devs) != tp or shapes == {leaf.shape}:
            raise AssertionError(f"{name} is not split over {tp} devices: "
                                 f"devices {sorted(devs)}, shards {shapes}")
    used = {}
    for d in jax.devices()[:tp]:
        stats = d.memory_stats()         # None on the CPU platform only
        if stats is None and d.platform == "cpu":
            continue
        used[d.id] = stats["bytes_in_use"]
    if not all(used.values()):
        raise AssertionError(f"a device holds no bytes: {used}")
    return used


def logit_oracle(params, cfg, cases, tol):
    """Teacher-force ``prompt + the engine's own output`` through the
    plain dense forward (no cache, no kernels, float32, 'highest') and
    measure, for every emitted token, how far its reference logit lies
    below the reference maximum at its position. Returns the worst gap
    per case; any gap over ``tol`` fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import llama
    ref_cfg = dataclasses.replace(cfg, use_kernels=False, remat=False,
                                  dtype=jnp.float32)
    width = max(len(p) + len(o) for p, o in cases)
    width = -(-width // 128) * 128                   # one compilation
    ids = np.zeros((len(cases), width), np.int32)
    nxt = np.zeros((len(cases), width), np.int32)
    emitted = np.zeros((len(cases), width), bool)
    for r, (prompt, out) in enumerate(cases):
        full = np.concatenate([prompt, np.asarray(out, np.int32)])
        ids[r, :len(full)] = full
        # position len(prompt)-1+i predicts out[i]
        lo = len(prompt) - 1
        nxt[r, lo:lo + len(out)] = out
        emitted[r, lo:lo + len(out)] = True

    def gaps(params, ids, nxt):
        logits = llama.forward(params, ids, ref_cfg).astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return logits.max(axis=-1) - chosen

    with jax.default_matmul_precision("highest"):
        gap = np.asarray(jax.jit(gaps)(params, jnp.asarray(ids),
                                       jnp.asarray(nxt)))
    worst = [float(gap[r][emitted[r]].max()) for r in range(len(cases))]
    if not all(np.isfinite(worst)) or max(worst) > tol:
        raise AssertionError(f"logit oracle: worst gap per case {worst} "
                             f"over the tolerance {tol}")
    return [round(w, 4) for w in worst]


def server_stages(clock, cfg, serving_config, int8_config, lengths, prefix,
                  new_tokens, seed=0, label=""):
    """The default server, the small int8 server, then the logit oracle
    over greedy requests of both (a short cold one, a chunked cold one, a
    prefix hit, a chunked prefix hit; the int8 engine's chunked one)."""
    import jax
    from paddle_tpu.models import llama
    params = llama.init_params(cfg, jax.random.PRNGKey(seed + 1))
    reqs = make_requests(cfg.vocab_size, lengths, prefix, new_tokens, seed)
    (streams, row), lap = timed_stage(clock, serve, params, cfg,
                                      serving_config, reqs)
    emit("server" + label, **row, **lap)
    picks = [12, 3, 14, 11]       # short, chunked, hit, chunked hit
    cases = [(reqs[i]["prompt"], streams[i]) for i in picks]
    int8_cases = []
    if int8_config is not None:
        small = [dict(r, shared=False) for r in
                 make_requests(cfg.vocab_size, lengths[:3] + lengths[1:2],
                               prefix, new_tokens[:1] * 2, seed + 7, n=4)]
        (s8, row8), lap = timed_stage(clock, serve, params, cfg,
                                      int8_config, small)
        emit("server_int8" + label, **row8, **lap)
        chunked = max(range(4), key=lambda i: len(small[i]["prompt"]))
        int8_cases = [(small[chunked]["prompt"], s8[chunked])]
    worst, lap = timed_stage(clock, logit_oracle, params, cfg, cases,
                             ORACLE_TOL["bf16"])
    out = {"bf16_worst_gap": worst, "bf16_tol": ORACLE_TOL["bf16"]}
    if int8_cases:
        w8, lap8 = timed_stage(clock, logit_oracle, params, cfg, int8_cases,
                               ORACLE_TOL["int8"])
        out.update(int8_worst_gap=w8, int8_tol=ORACLE_TOL["int8"])
        lap = {k: round(lap[k] + lap8[k], 2) for k in lap}
    emit("oracle" + label, **out, **lap)


# ---------------------------------------------------------------------------
# stage 6: four chips
# ---------------------------------------------------------------------------

def four_chip(clock, cfg, batch, seq, serving_config, lengths, prefix,
              new_tokens):
    """TP=4 serving, dp2 x mp2 training and the multi-chip dry run, in
    this same process, on the real devices."""
    import __graft_entry__
    server_stages(clock, cfg, dataclasses.replace(serving_config, tp=4),
                  None, lengths, prefix, new_tokens, label="_tp4")
    row, lap = timed_stage(clock, trainer, clock, cfg, batch, seq, 3,
                           dp=2, mp=2)
    emit("trainer_dp2_mp2", **row, **lap)
    _, lap = timed_stage(clock, __graft_entry__.dryrun_multichip, 4)
    emit("dryrun_multichip", families="A-G", devices=4, **lap)


def main() -> None:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke.py: jax.default_backend() is {backend!r}, not "
                 f"'tpu'; this script has no CPU mode")

    import jaxlib
    from paddle_tpu.inference.serving import ServingConfig
    from paddle_tpu.jit import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    emit("env", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=importlib.metadata.version("libtpu"),
         compile_cache_dir=cache_dir)

    cfg, batch, seq = preset()
    sc = ServingConfig()                             # every serving default
    sizes = dict(lengths=(32, 96, 300, 700), prefix=64, new_tokens=(16, 48))

    rows, lap = timed_stage(clock, kernel_rollcall, cfg, batch, seq, sc)
    emit("kernels", kernels=rows, **lap)

    row, lap = timed_stage(clock, trainer, clock, cfg, batch, seq, 5)
    emit("trainer", **row, **lap)

    int8 = ServingConfig(kv_quant="int8", quantize="int8", max_slots=4,
                         max_model_len=512)
    server_stages(clock, cfg, sc, int8, **sizes)

    if jax.device_count() >= 4:
        four_chip(clock, cfg, batch, seq, sc, **sizes)
    else:
        emit("four_chip", skipped=f"device_count={jax.device_count()}")

    print(json.dumps({"ok": True, "device": device_record()}), flush=True)


if __name__ == "__main__":
    main()
