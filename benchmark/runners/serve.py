"""A serving cell: seeded weights, one supervised engine behind the asyncio
front line, every shape warmed, load from one process, the window, the
drain, then the check against the plain reference.

The system under test is ``ServingServer(EngineSupervisor(params, cfg,
ServingConfig(**engine)))`` with ``engine`` taken from the configuration
file. Everything the client sees goes through ``ServingServer.agenerate``;
times are the client's (``time.perf_counter`` when a token event arrives).
"""

from __future__ import annotations

import asyncio
import gc
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, metrics, traffic

# Logit oracle: for each token the engine emitted greedily, the float32
# 'highest' reference logit of that token must lie within this distance of
# the reference maximum at its position. If the engine's logits are the
# reference's plus an error e, its argmax trails the reference maximum by
# at most 2*max|e|. With unit-variance products the logits over the
# vocabulary have a standard deviation near 1 and a maximum near 4-5.
# bfloat16 keeps 8 significand bits; through 16 layers of bf16 activations
# the logit error measured on the chip (26 runs, PR 23) was a worst gap of
# 0.050 (chip_smoke, PR 21: 0.008-0.053 over 12 layers). An engine
# computing below bf16 (fp8 / int8 activations or cache: errors some 8-16
# times larger) lands gaps of 0.3 and more, and a wrong block, a stale
# tail or a dropped chunk lands a token units below the maximum. 0.25 is
# about five times the measured worst gap.
ORACLE_TOL = 0.25


def warm_plan(mix: Dict[str, Any], engine: Dict[str, Any]) -> List[List[int]]:
    """Prompt lengths of the warm-up waves: one wave per (wave-size bucket,
    length bucket) the batched prefill can build for this mix's short
    prompts, then one two-chunk prompt per query bucket of the mixed
    step. A wave is submitted whole before the engine steps, so it is
    admitted as one batch. The length buckets are the engine's own
    (``ServingEngine._bucket``), so the plan follows the program."""
    from paddle_tpu.inference.serving import ServingEngine
    _bucket = ServingEngine._bucket
    chunk = int(engine["prefill_chunk"])
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    waves = []
    sb = _bucket(lo)
    while sb // 2 < min(hi, chunk) and sb <= chunk:
        bb = 1
        while bb <= min(int(mix["warm_wave_max"]), int(engine["max_slots"])):
            waves.append([min(sb, hi)] * bb)
            bb *= 2
        sb *= 2
    if hi > chunk:
        q = 8
        while q <= chunk:
            waves.append([chunk + q])
            q *= 2
    return waves


def warm_up(ctx, sup, vocab: int) -> None:
    """Run every wave of the plan through the supervisor, alone, with one
    sampling request in each so the sampler of that shape compiles too."""
    rng = np.random.default_rng(12345)
    for wave in warm_plan(ctx.mix, ctx.config["engine"]):
        for j, n in enumerate(wave):
            kw = {"max_new_tokens": 2, "eos_token_id": None}
            if j == 0:
                kw.update(ctx.mix["sampling"], seed=1)
            sup.submit(rng.integers(0, vocab, n).astype(np.int32), **kw)
        while sup.pending:
            sup.step()


class Monitor(threading.Thread):
    """Off the event loop: engine ``stats()`` at the window's edges and once
    a second inside it (it waits for the engine's lock, which a step holds,
    so it must not run on the loop that stamps token events), and the
    profiler's start and stop in a ``--trace 1`` run."""

    def __init__(self, ctx, engine, clock, t0: float, t1: float):
        super().__init__(daemon=True, name="bench-monitor")
        self.ctx, self.engine, self.clock = ctx, engine, clock
        self.t0, self.t1 = t0, t1
        self.samples: List[Dict[str, Any]] = []
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}
        self.compiles = [0, 0]
        self.traced = None
        self.memory_peak = 0

    def _sleep_until(self, t: float) -> None:
        d = t - time.perf_counter()
        if d > 0:
            time.sleep(d)

    def run(self) -> None:
        import jax
        ctx = self.ctx
        trace_s = float(ctx.mix.get("trace_s", 3.0))
        self._sleep_until(self.t0)
        self.compiles[0] = self.clock.compiles
        self.before = self.engine.stats()
        nxt = self.t0 + 1.0
        while nxt < self.t1:
            if ctx.trace and self.traced is None and \
                    nxt >= self.t1 - trace_s:
                self.traced = harness.TracedWindow(ctx.scratch,
                                                   ctx.cell["name"])
                self.traced.start()
            self._sleep_until(nxt)
            st = self.engine.stats()
            self.samples.append({"live_slots": st["live_slots"],
                                 "queued": st["queued"]})
            nxt += 1.0
        self._sleep_until(self.t1)
        self.compiles[1] = self.clock.compiles
        self.after = self.engine.stats()
        if self.traced is not None:
            self.traced.stop()
        self.memory_peak = harness.memory_peak_bytes(jax.local_devices())


async def _one(srv, req, rec, token_times):
    """One request through ``agenerate``; fills its record."""
    now = time.perf_counter
    rec["sent"] = now()
    try:
        async for ev in srv.agenerate(req["prompt"], **req["kw"]):
            kind = ev["type"]
            if kind == "start":
                rec["started"] = True
            elif kind == "token":
                t = now()
                if rec["first"] is None:
                    rec["first"] = t
                rec["last"] = t
                rec["n_out"] += 1
                token_times.append(t)
                if rec["tokens"] is not None:
                    rec["tokens"].append(ev["token"])
            elif kind == "finish":
                rec["state"] = ev.get("state")
            elif kind == "disconnect":
                rec["error"] = "disconnected (slow consumer)"
    except asyncio.CancelledError:
        rec["cancelled"] = True        # the harness closed the loop
        raise
    except Exception as e:             # noqa: BLE001 — refused or failed:
        rec["error"] = repr(e)         # counted, never raised to the loop
    rec["end"] = now()
    rec["ok"] = rec["error"] is None and rec["n_out"] == rec["want"]


def _record(req) -> Dict[str, Any]:
    return {"index": req["index"], "due": None, "sent": None, "first": None,
            "last": None, "end": None, "n_out": 0, "want": req["kw"]["max_new_tokens"],
            "ok": False, "error": None, "state": None, "cancelled": False,
            "started": False,
            "late": 0.0, "prompt_len": len(req["prompt"]),
            "tokens": None if req["sampled"] else []}


async def _load(ctx, srv, reqs, records, token_times, clock) -> Monitor:
    """Offer the load; returns the monitor, which holds the window's edges
    (fixed before any request is sent) and what it read between them."""
    mix, now = ctx.mix, time.perf_counter
    tasks: List[asyncio.Task] = []
    async with srv.running():
        start = now()
        t0 = start + float(mix["ramp_s"])
        t1 = t0 + ctx.seconds
        mon = Monitor(ctx, srv.sup.engine, clock, t0, t1)
        mon.start()

        current: Dict[int, Dict[str, Any]] = {}   # task number -> record

        def launch(k, req):
            rec = current[k] = _record(req)
            records.append(rec)
            return rec

        if mix["loop"] == "closed":
            feed = iter(reqs)
            clients = int(mix["clients"])

            async def client(i):
                # staggered start: the first wave is not one burst
                await asyncio.sleep(float(mix["ramp_s"]) * 0.5 * i / clients)
                while now() < t1:
                    req = next(feed, None)
                    if req is None:
                        raise RuntimeError("the request list ran out; "
                                           "raise 'requests' in the mix")
                    rec = launch(i, req)
                    rec["due"] = now()
                    await _one(srv, req, rec, token_times)
                    current.pop(i)

            tasks = [asyncio.create_task(client(i)) for i in range(clients)]
        else:
            for k, req in enumerate(reqs):
                due = start + req["due_s"]
                if due >= t1:
                    break
                with ctx.span("generator_wait"):
                    await asyncio.sleep(max(0.0, due - now()))
                rec = launch(k, req)
                rec["due"] = due
                rec["late"] = now() - due
                tasks.append(asyncio.create_task(
                    _one(srv, req, rec, token_times)))
        with ctx.span("window_wait"):
            await asyncio.sleep(max(0.0, t1 - now()))
        # the window is over: give open requests the drain, then cut the
        # rest. A task is cut only once its submit has been answered (the
        # pump must not find a cancelled future to answer).
        with ctx.span("draining"):
            drain = float(mix.get("drain_s", 0.0))
            if drain > 0 and tasks:
                await asyncio.wait(tasks, timeout=drain)
            open_ = {k: t for k, t in enumerate(tasks) if not t.done()}
            while open_:
                for k, t in open_.items():
                    if k not in current or current[k]["started"]:
                        t.cancel()
                await asyncio.wait(open_.values(), timeout=0.05)
                open_ = {k: t for k, t in open_.items() if not t.done()}
        for t in tasks:
            if not t.cancelled() and t.exception() is not None:
                raise t.exception()
    return mon


def oracle(ctx, params, records, reqs_by_index) -> Dict[str, Any]:
    """Teacher-force prompt + the engine's own greedy output through the
    plain reference, one sequence and one layer at a time (each layer's
    weights are upcast alone, so it fits beside the served weights), and
    measure how far each emitted token's reference logit lies under the
    row's maximum."""
    import jax
    import jax.numpy as jnp
    ref = harness.load_reference(ctx)
    cap = int(ctx.mix["oracle_max_len"])
    chunk = int(ctx.config["engine"]["prefill_chunk"])
    done = [r for r in records if r["ok"] and r["tokens"] is not None
            and r["prompt_len"] + r["n_out"] <= cap]
    # chunked prompts first (they cross the mixed step), then the rest
    done.sort(key=lambda r: (r["prompt_len"] <= chunk, r["index"]))
    picks = done[:int(ctx.mix["oracle_requests"])]
    if not picks:
        return {"ok": False, "why": "no finished greedy request to check"}
    model = ctx.config
    embed = jax.jit(ref.embed)
    layer = jax.jit(lambda x, stacked, i: ref.decoder_layer(
        x, ref.from_stacked({"layers": stacked}, i), model))

    def gaps(x, norm_w, lm_head, nxt):
        logits = ref.head(x, norm_w, lm_head, model)
        chosen = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return logits.max(axis=-1) - chosen

    gaps = jax.jit(gaps)
    n_layers = params["layers"]["wq"].shape[0]
    worst = []
    for r in picks:
        prompt = reqs_by_index[r["index"]]["prompt"]
        out = np.asarray(r["tokens"], np.int32)
        ids = np.zeros((cap,), np.int32)
        ids[:len(prompt) + len(out)] = np.concatenate([prompt, out])
        nxt = np.zeros((cap,), np.int32)
        lo = len(prompt) - 1         # position lo + i predicts out[i]
        nxt[lo:lo + len(out)] = out
        x = embed(jnp.asarray(ids), params["embed"])
        for i in range(n_layers):
            x = layer(x, params["layers"], jnp.int32(i))
        gap = np.asarray(gaps(x, params["ln_f"], params["lm_head"],
                              jnp.asarray(nxt)))[lo:lo + len(out)]
        worst.append(float(gap.max()) if np.isfinite(gap).all()
                     else float("inf"))
    return {"ok": max(worst) <= ORACLE_TOL, "worst_gap": worst,
            "tolerance": ORACLE_TOL,
            "checked": [(r["prompt_len"], r["n_out"]) for r in picks]}


def run(ctx) -> Dict[str, Any]:
    import jax
    from paddle_tpu.inference.serving import (EngineSupervisor,
                                              InvariantAuditor,
                                              ServingConfig, ServingServer)
    clock = metrics.CompileClock()
    mix, engine = ctx.mix, ctx.config["engine"]
    cfg = harness.llama_config(ctx.config, **ctx.config["program"])
    with ctx.span("weights"):
        params = jax.block_until_ready(harness.make_weights(cfg, ctx.seed))
    sup = EngineSupervisor(params, cfg, ServingConfig(**engine))
    with ctx.span("warm_up"):
        warm_up(ctx, sup, cfg.vocab_size)
    warm_compiles, warm_s = clock.compiles, clock.seconds
    eng = sup.engine
    reqs = traffic.make_requests(mix, cfg.vocab_size, ctx.seed,
                                 int(mix["requests"]))
    srv = ServingServer(sup)
    records: List[Dict[str, Any]] = []
    token_times: List[float] = []
    mon = asyncio.run(_load(ctx, srv, reqs, records, token_times, clock))
    mon.join(timeout=60)
    t0, t1 = mon.t0, mon.t1

    # ---- the system's own health
    health = {"pump_error": repr(srv.pump_error) if srv.pump_error else None,
              "restarts": sup.restarts, "broken": bool(sup.broken),
              "blocks_in_use": eng.cache.manager.blocks_in_use,
              "audit_violations": len(InvariantAuditor().quiesce(
                  eng, collect=True))}
    healthy = (health["pump_error"] is None and not health["restarts"]
               and not health["broken"] and not health["blocks_in_use"]
               and not health["audit_violations"])
    final_stats = eng.stats()

    # ---- window accounting (requests the harness itself cut are not
    # attempts that failed: a closed loop ends with its clients mid-request)
    if mix["loop"] == "open":
        judged = metrics.due_in_window(records, t0, t1)
        for r in judged:                # unfinished after the drain: failed
            r["ok"] = r["ok"] and not r["cancelled"]
    else:
        judged = [r for r in records
                  if r["end"] is not None and t0 <= r["end"] < t1]
    failed = metrics.count_failed(judged)
    good = [r for r in judged if r["ok"]]
    ttft = metrics.ttft_s(good)
    tpot = metrics.tpot_s(good)
    out_tokens = metrics.tokens_in_window(token_times, t0, t1)
    e2e = {"setup_s": ctx.setup_seconds(t0),
           "out_tokens_per_s": metrics.tapered_rate(
               token_times, t0, t1, float(mix["edge_s"]))}
    if ttft:
        p = metrics.percentile_with_missing(ttft, failed, 90)
        e2e["ttft_p90_ms"] = None if p is None else p * 1e3
    if tpot:
        p = metrics.percentile_with_missing(tpot, failed, 90)
        e2e["tpot_p90_ms"] = None if p is None else p * 1e3
    ctx.note(window_s=t1 - t0, judged=len(judged), failed=failed,
             out_tokens=out_tokens,
             ttft_ms={"n": len(ttft), "p50": metrics.percentile(ttft, 50) * 1e3
                      if ttft else None},
             tpot_ms={"n": len(tpot), "p50": metrics.percentile(tpot, 50) * 1e3
                      if tpot else None},
             in_system_each_second=[s["live_slots"] + s["queued"]
                                    for s in mon.samples],
             tokens_each_second=np.histogram(
                 token_times, bins=max(1, int(t1 - t0)),
                 range=(t0, t1))[0].tolist(),
             compiles={"warm_up": warm_compiles, "warm_up_s": warm_s,
                       "in_window": mon.compiles[1] - mon.compiles[0]},
             health=health,
             counters={k: final_stats[k] for k in (
                 "admitted", "retired", "preemptions", "recomputed_tokens",
                 "prefix_hit_tokens", "prefill_dispatches",
                 "decode_dispatches", "mixed_dispatches", "decode_traces",
                 "mixed_traces", "prefill_traces", "sample_traces", "shed",
                 "usable_blocks", "kv_pool_mb", "paged_kernel")},
             dispatch_latency=final_stats["dispatch_latency"],
             gen_late_ms_max=max([r["late"] for r in records] or [0]) * 1e3)

    # ---- correctness, outside the window, after the pool is released
    traced = None
    if mon.traced is not None:
        with ctx.span("reduce_trace"):
            traced = mon.traced.reduce(int(ctx.cell["chips"]))
    del srv, sup, eng
    mon.engine = None
    gc.collect()
    by_index = {r["index"]: r for r in reqs}
    check = oracle(ctx, params, records, by_index)
    ctx.note(oracle=check)
    return {
        "correct": bool(healthy and check["ok"]),
        "attempted": len(judged), "failed": failed,
        "end_to_end": e2e, "memory_peak_bytes": mon.memory_peak,
        "trace": traced,
        # what the layer-metric readers read
        "records": judged, "all_records": records,
        "stats_before": mon.before, "stats_after": mon.after,
        "samples": mon.samples, "max_slots": int(engine["max_slots"]),
        "compiles_in_window": mon.compiles[1] - mon.compiles[0],
        "window_s": t1 - t0,
    }
