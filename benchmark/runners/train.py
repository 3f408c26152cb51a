"""A training cell: seeded weights, the program's loss held to the plain
reference, then donated ``jit_step`` train steps, one dispatched ahead of
the one being waited for, on a new seeded batch every step.

The system under test is ``llama.make_train_step`` through
``jit.train_step.jit_step`` with the trainer settings of the
configuration file; with a ``mesh`` in the file the same step runs over
``HybridCommunicateGroup(dp, mp)`` under ``jax.set_mesh`` with the
parameters laid out by ``llama.param_specs(mp_axis="mp")``.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import time
from typing import Any, Dict

import numpy as np

from benchmark import flops, harness, metrics, traffic

# The program computes in bfloat16 with float32 parameters; the reference
# is float32 'highest'. On one seeded sequence of 1024 tokens the loss of
# random weights is near ln(vocab) = 10.4-10.9. bf16 activations put an
# error of a few 1e-2 on single logits, which averages over the sequence
# to a few 1e-4 on the mean loss; gradients carry bf16's 2^-8 relative
# rounding per element, most of which cancels in the norm. Measured on the
# chip at the published widths (13 runs, 7 seeds, PR 23): loss within 1.0e-3,
# gradient norm within 0.10%. The bounds are about five times that, and far
# inside what a dropped layer or a wrong mask (tens of percent on the
# gradient norm) or fp8-grade arithmetic (percents) would do.
LOSS_TOL = 5e-3          # absolute, on a loss near 10.9
GRAD_NORM_RTOL = 5e-3    # relative


def labels_for(ids: np.ndarray) -> np.ndarray:
    """Each position predicts the next token; the last has none."""
    lab = np.full_like(ids, -100)
    lab[:, :-1] = ids[:, 1:]
    return lab


def check_against_reference(ctx, cfg, params, under_mesh) -> Dict[str, Any]:
    """Loss and gradient norm of one seeded sequence: the program's loss
    function against the reference's, before any optimizer state exists."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    ref = harness.load_reference(ctx)
    seq = int(ctx.mix["check_seq"])
    rows = int(ctx.mix.get("check_rows", 1))
    ids = traffic.train_batch(cfg.vocab_size, rows, seq, ctx.seed, 10 ** 9)
    lab = labels_for(ids)

    def program(params, ids, lab):
        loss, grads = jax.value_and_grad(llama.loss_fn)(params, ids, lab, cfg)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree_util.tree_leaves(grads))
        return loss, jnp.sqrt(sq)

    def reference(params, ids, lab):
        return ref.loss_and_grad_norm(ref.from_program(params), ids, lab,
                                      ctx.config)

    with under_mesh():
        got = jax.jit(program)(params, jnp.asarray(ids), jnp.asarray(lab))
    want = jax.jit(reference)(params, jnp.asarray(ids), jnp.asarray(lab))
    got = [float(x) for x in got]
    want = [float(x) for x in want]
    ok = (abs(got[0] - want[0]) <= LOSS_TOL and
          abs(got[1] - want[1]) <= GRAD_NORM_RTOL * want[1])
    return {"ok": bool(ok), "loss": got[0], "ref_loss": want[0],
            "grad_norm": got[1], "ref_grad_norm": want[1],
            "loss_tol": LOSS_TOL, "grad_norm_rtol": GRAD_NORM_RTOL}


def run(ctx) -> Dict[str, Any]:
    import jax
    from paddle_tpu.jit.train_step import jit_step
    from paddle_tpu.models import llama
    clock = metrics.CompileClock()
    mix, trainer = ctx.mix, ctx.config["trainer"]
    cfg = harness.llama_config(ctx.config, **ctx.config["program"])
    batch, seq = int(trainer["batch"]), int(mix["seq"])
    chips = int(ctx.cell["chips"])
    devices = jax.devices()[:chips]
    under_mesh = contextlib.nullcontext
    shardings, put = None, jax.numpy.asarray
    if trainer.get("mesh"):
        from jax.sharding import NamedSharding
        from paddle_tpu.distributed.topology import HybridCommunicateGroup
        hcg = HybridCommunicateGroup(devices=devices, **trainer["mesh"])
        under_mesh = functools.partial(jax.set_mesh, hcg.mesh)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(hcg.mesh, s),
            llama.param_specs(cfg, mp_axis="mp"),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        data = NamedSharding(hcg.mesh, llama.batch_spec(("dp", "sharding")))
        put = functools.partial(jax.device_put, device=data)
    with ctx.span("weights"):
        params = jax.block_until_ready(
            harness.make_weights(cfg, ctx.seed, shardings))
    with ctx.span("reference_check"):
        check = check_against_reference(ctx, cfg, params, under_mesh)
    ctx.note(reference_check=check)

    init_opt, step_fn = llama.make_train_step(cfg, lr=float(trainer["lr"]))
    opt = init_opt(params)
    jstep = jit_step(step_fn, donate_argnums=(0, 1))
    warm = int(mix["warm_steps"])
    trace_s = float(mix.get("trace_s", 3.0))
    pending: collections.deque = collections.deque()
    finish, losses, compiles_at = [], [], []
    traced, t0, step = None, None, 0
    now = time.perf_counter
    while True:
        with ctx.span("make_batch"):
            ids = traffic.train_batch(cfg.vocab_size, batch, seq, ctx.seed,
                                      step)
            ids_d, lab_d = put(ids), put(labels_for(ids))
        with ctx.span("dispatch"), under_mesh():
            params, opt, loss = jstep(params, opt, ids_d, lab_d)
        pending.append(loss)
        step += 1
        if len(pending) < 2:
            continue
        with ctx.span("wait_step"):
            losses.append(pending.popleft().block_until_ready())
        finish.append(now())
        compiles_at.append(clock.compiles)
        if len(finish) == warm:
            t0 = finish[-1]            # pipeline full, every program built
        if t0 is None:
            continue
        elapsed = finish[-1] - t0
        if elapsed >= ctx.seconds:
            break
        if ctx.trace and traced is None and \
                elapsed >= ctx.seconds - trace_s:
            traced = harness.TracedWindow(ctx.scratch, ctx.cell["name"])
            traced.start()
    if traced is not None:
        traced.stop()
    pending.popleft().block_until_ready()      # the step dispatched ahead
    memory_peak = harness.memory_peak_bytes(devices)

    rate = metrics.whole_step_rate(finish, t0, ctx.seconds, batch * seq)
    measured = [t for t in finish if t >= t0]
    step_s = np.diff(measured)
    loss_values = [float(x) for x in losses]
    finite = bool(np.isfinite(loss_values).all())
    in_window = compiles_at[-1] - compiles_at[warm - 1]
    kind = jax.devices()[0].device_kind
    per_token = flops.train_flops_per_token(ctx.config, seq)
    ctx.note(steps=rate["steps"], window_s=rate["window_s"],
             step_ms_p50=float(np.median(step_s)) * 1e3,
             tokens_per_step=batch * seq, flops_per_token=per_token,
             losses=[round(x, 4) for x in loss_values[:3] + loss_values[-2:]],
             compiles={"total": clock.compiles, "seconds": clock.seconds,
                       "in_window": in_window})
    trace_out = None
    if traced is not None:
        trace_out = traced.reduce(chips)
    return {
        "correct": bool(check["ok"] and finite),
        "attempted": rate["steps"], "failed": 0,
        "end_to_end": {"setup_s": ctx.setup_seconds(t0),
                       "train_tokens_per_s": rate["tokens_per_s"]},
        "memory_peak_bytes": memory_peak, "trace": trace_out,
        "step_s": [float(x) for x in step_s],
        "tokens_per_s": rate["tokens_per_s"], "chips": chips,
        "flops_per_token": per_token, "device_kind": kind,
        "compiles_in_window": in_window, "window_s": rate["window_s"],
    }
