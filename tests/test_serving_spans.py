"""The measurement inside the serving path (ISSUE 24): the cumulative
``profiler.SpanStats`` aggregator by hand, the flat ``serve:*`` phase
spans of the pump thread (they close the thread's wall time, none
encloses another, they are in a profiler trace with ``step``/``kind``),
the lane and iteration counters against a hand count, the queue-wait
histogram through the server's command queue, and a stable name on every
kernel and every jitted program in the TPU lowering."""

import asyncio
import glob
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import profiler
from paddle_tpu.inference.serving import (EngineSupervisor, ServingConfig,
                                          ServingEngine, ServingServer)
from paddle_tpu.models.llama import LlamaConfig, init_params

CFG = LlamaConfig(vocab_size=97, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=96)
SC = dict(block_size=4, max_slots=2, max_model_len=48, decode_chunk=2,
          queue_depth=16, prefill_chunk=8)
ENGINE_SPANS = ("serve:plan", "serve:operands", "serve:dispatch",
                "serve:fetch", "serve:commit", "serve:journal")
PUMP_SPANS = ENGINE_SPANS + ("serve:idle", "serve:cmds", "serve:route",
                             "serve:deliver", "serve:supervise")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def programs(params):
    """One compiled program set for every engine of this module."""
    return ServingEngine(params, CFG, ServingConfig(**SC)).programs


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# the aggregator, by hand
# ---------------------------------------------------------------------------

class TestSpanStats:
    def test_counts_totals_and_kinds(self):
        s = profiler.SpanStats()
        s.add("a", 0.25)
        s.add("a", 0.5, "decode")
        s.add("a", 1.0, "mixed")
        s.add("a", 2.0, "mixed")
        with s.span("b", "decode", step=4) as sp:
            time.sleep(0.01)
        s.count("lanes", 3)
        s.count("lanes", 4)
        snap = s.snapshot()
        assert snap["spans"]["a"] == {
            "count": 4, "seconds": 3.75,
            "kinds": {"decode": {"count": 1, "seconds": 0.5},
                      "mixed": {"count": 2, "seconds": 3.0}}}
        b = snap["spans"]["b"]
        assert b["count"] == 1 and b["kinds"]["decode"]["count"] == 1
        # the span's seconds ARE its two stamps, left readable
        assert b["seconds"] == pytest.approx(sp.t1 - sp.t0) and \
            b["seconds"] >= 0.01
        assert snap["counters"] == {"lanes": 7}

    def test_snapshot_is_plain_data(self):
        s = profiler.SpanStats()
        s.add("a", 0.1, "decode")
        s.count("c")
        s.observe("h", 0.02)
        snap = s.snapshot()
        assert json.loads(json.dumps(snap)) == snap
        s.add("a", 0.1)                      # a snapshot is a copy
        assert snap["spans"]["a"]["count"] == 1

    def test_histograms_subtract_to_a_window(self):
        s = profiler.SpanStats()
        for v in (0.001, 0.001, 0.002):      # before the window
            s.observe("wait", v)
        before = s.snapshot()
        window = [0.010] * 50 + [0.100] * 40 + [1.0] * 10
        for v in window:
            s.observe("wait", v)
        after = s.snapshot()
        d = profiler.snapshot_delta(after, before)["histograms"]["wait"]
        assert d["count"] == 100
        assert d["sum"] == pytest.approx(sum(window))
        assert d["cumulative"][-1] == 100 and \
            all(x <= y for x, y in zip(d["cumulative"], d["cumulative"][1:]))
        # a bucket is 33% wide: the percentile lands inside the bucket
        # that holds the sample of that rank
        for q, want in ((25, 0.010), (50, 0.010), (75, 0.100),
                        (90, 0.100), (95, 1.0)):
            got = profiler.histogram_percentile(d, q)
            assert want / 1.34 <= got <= want * 1.001, (q, got)
        # the lifetime histogram still holds the three early samples
        life = after["histograms"]["wait"]
        assert life["count"] == 103
        assert profiler.histogram_percentile(life, 1) < 0.0011
        assert profiler.histogram_percentile(
            {"le": d["le"], "cumulative": [0] * len(d["cumulative"]),
             "count": 0, "sum": 0.0}, 50) is None

    def test_spans_and_counters_subtract(self):
        s = profiler.SpanStats()
        s.add("a", 1.0, "decode")
        s.count("n", 2)
        before = s.snapshot()
        s.add("a", 0.5, "decode")
        s.add("new", 0.25)
        s.count("n", 5)
        d = profiler.snapshot_delta(s.snapshot(), before)
        assert d["spans"]["a"] == {
            "count": 1, "seconds": 0.5,
            "kinds": {"decode": {"count": 1, "seconds": 0.5}}}
        assert d["spans"]["new"] == {"count": 1, "seconds": 0.25}
        assert d["counters"] == {"n": 5}

    def test_record_event_and_summary_read_the_same_class(self, capsys):
        assert isinstance(profiler.host_events, profiler.SpanStats)
        with profiler.RecordEvent("before_the_profiler"):
            pass
        prof = profiler.Profiler(timer_only=True)
        prof.start()
        with profiler.RecordEvent("inside"):
            time.sleep(0.002)
        prof.stop()
        out = prof.summary()
        capsys.readouterr()
        # summary() is what the aggregator gained since start()
        assert "inside" in out and "before_the_profiler" not in out
        assert profiler.host_events.snapshot()["spans"]["inside"][
            "count"] >= 1


# ---------------------------------------------------------------------------
# counters against a hand count
# ---------------------------------------------------------------------------

def test_lane_and_iteration_counters_by_hand(params, programs):
    """M = 2 slots, prefill_chunk 8. A: a 5-token prompt (batched prefill,
    bucket 8) with 4 new tokens; B: a 20-token prompt (chunks 8, 8, 4
    through the mixed step) with 3 new tokens."""
    eng = ServingEngine(params, CFG, ServingConfig(**SC), programs=programs)

    def counters():
        return dict(eng.stats()["spans"]["counters"])

    assert eng.health_snapshot()["real_lane_pct"] == {
        "mixed": None, "prefill": None}
    assert eng.health_snapshot()["short_row_pct"] is None
    eng.submit(prompt(5), max_new_tokens=4, eos_token_id=None)
    eng.step(max_iters=1)        # prefill wave of one + one decode iteration
    c = counters()
    assert (c["prefill_lanes_real"], c["prefill_lanes_total"]) == (5, 8)
    assert c["decode_iterations"] == 1
    assert "mixed_lanes_total" not in c
    eng.submit(prompt(20, 1), max_new_tokens=3, eos_token_id=None)
    eng.step(max_iters=1)        # mixed: B's chunk of 8 + A's decode lane
    c = counters()
    assert (c["mixed_lanes_real"], c["mixed_lanes_total"]) == (9, 16)
    assert (c["attn_rows_short"], c["attn_rows"]) == (1, 2)
    eng.step(max_iters=1)        # mixed: chunk of 8 + A's last token
    c = counters()
    assert (c["mixed_lanes_real"], c["mixed_lanes_total"]) == (18, 32)
    eng.step(max_iters=1)        # mixed: the 4-token tail alone (Q = 8)
    c = counters()
    assert (c["mixed_lanes_real"], c["mixed_lanes_total"]) == (22, 48)
    # rows that took the kernel's short tile: A's two decode lanes, of 5
    assert (c["attn_rows_short"], c["attn_rows"]) == (2, 5)
    assert c["decode_iterations"] == 1       # mixed steps are not counted
    eng.step()                   # B's remaining 2 tokens in ONE dispatch
    c = counters()
    assert c["decode_iterations"] == 3
    assert not eng.pending
    st = eng.stats()
    assert (st["prefill_dispatches"], st["mixed_dispatches"],
            st["decode_dispatches"]) == (1, 3, 2)
    # every dispatch is one serve:dispatch and one serve:fetch, by kind
    sp = st["spans"]["spans"]
    assert sp["serve:fetch"]["count"] == st["chunks"] == 6
    assert {k: v["count"] for k, v in
            sp["serve:dispatch"]["kinds"].items()} == {
        "prefill": 1, "mixed": 3, "decode": 2}
    # dispatch_latency is fed from the same two stamps
    lat = st["dispatch_latency"]["mixed"]
    assert lat["count"] == 3 and lat["p50_ms"] > 0
    assert st["spans"]["histograms"]["prefill_s"]["count"] == 2
    snap = eng.health_snapshot()
    assert set(snap["phase_ms_per_step"]) == {
        n[len("serve:"):] for n in ENGINE_SPANS}
    assert snap["request_wait"]["queue_wait_p50_s"] is not None
    # the operator's reading of the lane counters: 22 of 48, 5 of 8
    assert snap["real_lane_pct"] == {"mixed": 45.83, "prefill": 62.5}
    assert snap["short_row_pct"] == 40.0
    json.dumps(snap)


# ---------------------------------------------------------------------------
# the pump thread: its spans close its wall time, and are flat in a trace
# ---------------------------------------------------------------------------

async def _collect(srv, p, **kw):
    return [ev async for ev in srv.agenerate(p, eos_token_id=None, **kw)]


async def _traffic(srv, n=6, new=10):
    """Short and chunked prompts together: prefill waves, mixed steps and
    decode dispatches all occur."""
    lens = [5, 20, 7, 30, 6, 18]
    return await asyncio.gather(*(
        _collect(srv, prompt(lens[i % len(lens)], i), max_new_tokens=new)
        for i in range(n)))


def test_pump_spans_close_the_threads_wall_time(params, programs):
    # decode dispatches of up to 8 iterations: at this toy size a step is
    # a few milliseconds, and the ~0.1 ms a step between spans (loop
    # control, locks, the spans' own bookkeeping) should stay well inside
    # the 5% (the iteration bound is a device scalar: same programs)
    sup = EngineSupervisor(params, CFG,
                           ServingConfig(**{**SC, "decode_chunk": 8}),
                           programs=programs)
    srv = ServingServer(sup)
    eng = sup.engine

    async def go():
        async with srv.running():
            await _traffic(srv, 2, 2)               # every shape compiled
            t0, a = time.perf_counter(), eng.stats()
            while time.perf_counter() - t0 < 1.5:
                await _traffic(srv, new=17)
            b, t1 = eng.stats(), time.perf_counter()
            return a, b, t1 - t0

    a, b, wall = asyncio.run(go())
    d = profiler.snapshot_delta(b["spans"], a["spans"])
    assert set(d["spans"]) == set(PUMP_SPANS)
    covered = sum(row["seconds"] for row in d["spans"].values())
    # stats() itself holds the engine lock between the pump's spans; a
    # span open at either edge is cut: both are far inside 5%
    assert covered == pytest.approx(wall, rel=0.05)
    chunks = b["chunks"] - a["chunks"]
    assert chunks > 0 and d["spans"]["serve:fetch"]["count"] == chunks
    by_kind = {k: v["count"]
               for k, v in d["spans"]["serve:dispatch"]["kinds"].items()}
    assert by_kind == {k: b[k + "_dispatches"] - a[k + "_dispatches"]
                       for k in ("prefill", "mixed", "decode")}
    assert all(n > 0 for n in by_kind.values())
    assert sup.restarts == 0 and srv.pump_error is None


def test_queue_wait_includes_the_wait_in_the_command_queue(params,
                                                           programs):
    """The request is stamped on the event loop when it is handed to the
    server; it then sits in ``_cmds`` until the pump takes it. The engine's
    own ``ttft_s`` starts at the engine's submit and misses that wait."""
    sup = EngineSupervisor(params, CFG, ServingConfig(**SC),
                           programs=programs)
    srv = ServingServer(sup)

    async def go():
        srv._loop = asyncio.get_running_loop()      # no pump thread yet
        task = asyncio.create_task(_collect(srv, prompt(6),
                                            max_new_tokens=2))
        await asyncio.sleep(0.3)                    # the planted wait
        assert srv._cmds.qsize() == 1
        async with srv.running():
            events = await task
        return events

    events = asyncio.run(go())
    fin = [e for e in events if e["type"] == "finish"][0]
    h = sup.engine.stats()["spans"]["histograms"]["queue_wait_s"]
    assert h["count"] == 1 and h["sum"] >= 0.3
    assert profiler.histogram_percentile(h, 50) >= 0.3 / 1.34
    assert fin["ttft_s"] < h["sum"]
    assert sup.engine.health_snapshot()["request_wait"][
        "queue_wait_p99_s"] >= 0.3 / 1.34


def test_a_drain_stops_the_command_sweep_and_keeps_the_order(params,
                                                             programs):
    """A drain steps the engine, so it runs outside ``serve:cmds``: the
    sweep stops at it and the pump runs it. What was queued behind it in
    the same sweep is answered after the drain, refused as draining."""
    import concurrent.futures

    from paddle_tpu.inference.serving import ServingUnavailable
    sup = EngineSupervisor(params, CFG, ServingConfig(**SC),
                           programs=programs)
    srv = ServingServer(sup)                        # pump driven by hand
    futs = [concurrent.futures.Future() for _ in range(3)]
    job = {"prompt": prompt(6), "max_new_tokens": 3}
    srv._cmds.put(("submit", dict(job), None, futs[0]))
    srv._cmds.put(("drain", 30.0, None, futs[1]))
    srv._cmds.put(("submit", dict(job), None, futs[2]))
    srv._pump_once()
    assert futs[0].done() and futs[1].done() and not futs[2].done()
    report = futs[1].result()
    assert report is srv.drain_report and report["completed"] == 1
    sp = sup.engine.stats()["spans"]["spans"]
    # the drain's engine steps were not inside the one serve:cmds span
    assert sp["serve:cmds"]["count"] == 1
    # (had they been, it would hold all of their phases; one phase alone
    # is a tenth of a millisecond on warm programs, too close to compare)
    assert sp["serve:cmds"]["seconds"] < sum(
        sp[n]["seconds"] for n in ENGINE_SPANS if n in sp)
    srv._pump_once()
    with pytest.raises(ServingUnavailable) as ei:
        futs[2].result(timeout=0)
    assert ei.value.reason == "draining"


def test_trace_holds_flat_serve_spans_with_step_and_kind(params, programs,
                                                         tmp_path):
    sup = EngineSupervisor(params, CFG, ServingConfig(**SC),
                           programs=programs)
    srv = ServingServer(sup)

    async def go():
        async with srv.running():
            await _traffic(srv, 2, 2)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                await _traffic(srv)
                await asyncio.sleep(0.1)            # the pump goes idle
            finally:
                jax.profiler.stop_trace()

    asyncio.run(go())
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)) for e in ln.events
              if e.name.startswith("serve:")]
             for p in jax.profiler.ProfileData.from_file(path).planes
             for ln in p.lines]
    lines = [ln for ln in lines if ln]
    assert len(lines) == 1                   # all on the pump thread
    spans = sorted(lines[0], key=lambda e: e[1])
    assert {e[0] for e in spans} == set(PUMP_SPANS)
    for (_, _, end, _), (name, start, _, _) in zip(spans, spans[1:]):
        assert start >= end, f"{name} opens inside another span"
    for name, _, _, args in spans:
        assert args["step"] >= 1, name
        at_dispatch = name in ("serve:operands", "serve:dispatch",
                               "serve:fetch", "serve:commit")
        assert ("kind" in args) == at_dispatch, (name, args)
    kinds = {args["kind"] for _, _, _, args in spans if "kind" in args}
    assert kinds == {"prefill", "mixed", "decode"}
    # one step's spans share its number, in the order the step runs them
    step = next(a["step"] for n, _, _, a in spans if a.get("kind") ==
                "mixed")
    names = [n for n, _, _, a in spans if a["step"] == step
             and n in ENGINE_SPANS]
    assert names[0] == "serve:plan"
    assert names[-5:] == ["serve:operands", "serve:dispatch", "serve:fetch",
                          "serve:commit", "serve:journal"]


# ---------------------------------------------------------------------------
# names: every kernel and every jitted program, in the TPU lowering
# ---------------------------------------------------------------------------

def _shapes(args):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype),
        args)


def test_paged_programs_and_kernels_carry_names_in_the_tpu_lowering(
        monkeypatch):
    """``jax.export(platforms=["tpu"])`` runs the Pallas TPU lowering with
    no chip: the module is named after the jitted function and each
    custom call after its ``pallas_call``'s ``name=``. These are the
    names a chip trace prints."""
    import dataclasses

    from paddle_tpu.kernels import dispatch
    cfg = dataclasses.replace(CFG, vocab_size=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    sc = ServingConfig(**{**SC, "paged_kernel": "on", "spec_decode": 2,
                          "spec_ngram": 2})
    eng = ServingEngine(params, cfg, sc)
    calls = {}

    def recorded(eng, attr):
        jitted = getattr(eng, attr)

        def call(*args):
            calls.setdefault(attr, (jitted, _shapes(args)))
            return jitted(*args)

        setattr(eng, attr, call)

    for attr in ("_jprefill", "_jdecode", "_jmixed", "_jspec", "_jsample"):
        recorded(eng, attr)
    # "c 0 c 1 ... c 15 c" holds the bigram (c, x) for every token x, so
    # prompt lookup must draft (the verify program runs); it is longer
    # than a chunk (the mixed program runs); the short sampled prompt
    # takes the batched prefill and the sampler
    rep = np.full((2 * cfg.vocab_size + 1,), 5, np.int32)
    rep[1::2] = np.arange(cfg.vocab_size)
    eng.submit(rep, max_new_tokens=6, eos_token_id=None)
    eng.submit(rep[:6], max_new_tokens=3, eos_token_id=None,
               temperature=0.8, top_k=8, seed=1)
    while eng.pending:
        eng.step()
    want = {"_jprefill": ("jit_paged_prefill", []),
            "_jdecode": ("jit_paged_decode", ["paged_attention_q1"]),
            "_jmixed": ("jit_paged_mixed", ["paged_attention_mq"]),
            "_jspec": ("jit_paged_spec", ["paged_attention_mq"]),
            "_jsample": ("jit_sample_tokens", [])}
    assert set(calls) == set(want)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def retraced(jitted):
        """The engine's function under its own name, behind a new function
        object: jax would otherwise reuse the CPU run's trace, in which
        the kernel is its interpret-mode expansion."""
        fn = jitted.__wrapped__

        def program(*args):
            return fn(*args)

        program.__name__ = fn.__name__
        return jax.jit(program)

    for attr, (jitted, shapes) in calls.items():
        text = jax.export.export(retraced(jitted), platforms=["tpu"])(
            *shapes).mlir_module()
        module, kernels = want[attr]
        assert f"module @{module} " in text, (attr, text[:200])
        assert "_unknown" not in text
        # every custom call of the module is a kernel with its name
        assert text.count("tpu_custom_call") == len(kernels), attr
        for k in kernels:
            assert f'kernel_name = "{k}"' in text, (attr, k)


def test_train_step_and_its_kernels_carry_names_in_the_tpu_lowering(
        monkeypatch):
    from paddle_tpu.jit.train_step import jit_step
    from paddle_tpu.kernels import dispatch
    from paddle_tpu.models import llama
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cfg = LlamaConfig(vocab_size=256, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=256,
                      use_kernels=True, remat=True, dtype=jnp.bfloat16,
                      param_dtype=jnp.float32)
    init_opt, step_fn = llama.make_train_step(cfg, lr=1e-3)
    shapes = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(init_opt, shapes)
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    jstep = jit_step(step_fn, donate_argnums=(0, 1))
    text = jax.export.export(jstep._jitted, platforms=["tpu"])(
        shapes, opt, ids, ids).mlir_module()
    assert "module @jit_train_step " in text
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name in text, name


def test_every_pallas_call_has_a_name():
    """Ten call sites, fifteen names (the paged kernel has four, by form,
    and a latent form; the grouped matmul two, gated or plain)."""
    import os
    import re
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "kernels")
    src = "".join(open(os.path.join(root, f)).read()
                  for f in sorted(os.listdir(root)) if f.endswith(".py"))
    assert len(re.findall(r"pl\.pallas_call\(", src)) == 10
    # a name is a literal on its ``name=`` line, or one of the paged
    # kernel's four (by form) in the table that line indexes
    names = re.findall(r'"(\w+)"', "".join(
        re.findall(r" name=(.*)", src) +
        re.findall(r"_NAMES = \(([^#]*?)\)\n\n", src, re.S)))
    assert sorted(names) == sorted([
        "paged_attention_q1", "paged_attention_mq",
        "paged_attention_window_q1", "paged_attention_window_mq",
        "flash_attention_fwd",
        "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
        "quant_matmul", "rms_norm_fwd", "rms_norm_bwd", "rope",
        "paged_attention_latent", "moe_grouped_matmul_gated",
        "moe_grouped_matmul"])
