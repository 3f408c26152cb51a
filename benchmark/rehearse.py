"""Compile a cell's programs at their real sizes for a DESCRIBED v5e (no
chip attached) and print what each asks of a chip's memory. Run by hand,
never by the tests:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell> \
        [--num-blocks N] [--batch B]

It runs libtpu's own compiler (Mosaic layouts, VMEM limits, HBM fit), so a
program the chip would refuse is refused here, at no chip time. It cannot
say anything about results or speed. ``--num-blocks`` and ``--batch``
override the configuration file so that the sizes written there can be
found: the KV pool that fits beside the weights and the largest program's
temporaries, and the largest power-of-two train batch that compiles.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"program": name,
           "arguments_gb": m.argument_size_in_bytes / 1e9,
           "outputs_gb": m.output_size_in_bytes / 1e9,
           "aliased_gb": m.alias_size_in_bytes / 1e9,
           "temporaries_gb": m.temp_size_in_bytes / 1e9,
           "code_gb": m.generated_code_size_in_bytes / 1e9}
    row["total_gb"] = (row["arguments_gb"] + row["outputs_gb"] -
                       row["aliased_gb"] + row["temporaries_gb"] +
                       row["code_gb"])
    print(json.dumps(row), flush=True)
    return row


def serve(config, mix, where, num_blocks):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness
    from benchmark.runners.serve import warm_plan
    from paddle_tpu.inference.serving import ServingConfig, ServingEngine
    from paddle_tpu.models import llama
    engine = dict(config["engine"])
    real_blocks = int(num_blocks or engine["num_blocks"])
    engine["num_blocks"] = 8          # the engine allocates its pool: tiny
    cfg = harness.llama_config(config, **config["program"])

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree)

    params = struct(jax.eval_shape(functools.partial(llama.init_params, cfg),
                                   jax.random.key(0)))
    eng = ServingEngine(params, cfg, ServingConfig(**engine))
    pool = struct(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((a.shape[0], real_blocks) +
                                       a.shape[2:], a.dtype)
        if a.ndim >= 2 else a, eng.cache.pool))
    pool_gb = sum(np.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(pool)) / 1e9
    weights_gb = sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(params)) / 1e9
    print(json.dumps({"weights_gb": weights_gb, "pool_gb": pool_gb,
                      "num_blocks": real_blocks, "pool_shapes": {
                          k: list(v.shape) for k, v in pool.items()}}))
    M, W = engine["max_slots"], eng.cache.blocks_per_seq

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    knobs = (s((M, 2), u32), s((M,), i32), s((M,), f32), s((M,), i32),
             s((M,), f32))
    rows = [report("decode", eng._jdecode.lower(
        params, pool, s((M,), i32), s((M,), i32), s((M,), i32),
        s((M,), bool), s((M, W), i32), s((M,), i32), s((), i32),
        *knobs).compile())]
    waves = warm_plan(mix, config["engine"])
    chunk = engine["prefill_chunk"]
    q = 8
    while q <= chunk:
        rows.append(report(f"mixed Q={q}", eng._jmixed.lower(
            params, pool, s((M, q), i32), s((M,), i32), s((M,), i32),
            s((M,), bool), s((M, W), i32), *knobs).compile()))
        q *= 2
    shapes = sorted({(len(w), eng._bucket(w[0])) for w in waves
                     if w[0] <= chunk})
    for bb, sb in shapes:
        rows.append(report(f"prefill B={bb} S={sb}", eng._jprefill.lower(
            params, s((bb, sb), i32), s((bb,), i32), s((bb, W), i32), pool,
            s((bb,), bool)).compile()))
    for bb in sorted({b for b, _ in shapes}):
        rows.append(report(f"sample B={bb}", eng._jsample.lower(
            s((bb, cfg.vocab_size), f32), s((bb, 2), u32), s((bb,), i32),
            s((bb,), f32), s((bb,), i32), s((bb,), f32)).compile()))
    worst = max(rows, key=lambda r: r["total_gb"])
    print(json.dumps({"largest_program": worst["program"],
                      "its_total_gb": worst["total_gb"],
                      "its_temporaries_gb": worst["temporaries_gb"]}))


def train(config, mix, topo, batch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    import numpy as np
    from benchmark import harness
    from paddle_tpu.models import llama
    trainer = config["trainer"]
    batch = int(batch or trainer["batch"])
    seq = int(mix["seq"])
    cfg = harness.llama_config(config, **config["program"])
    shapes = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.key(0))
    under = contextlib.nullcontext
    if trainer.get("mesh"):
        dp, mp = trainer["mesh"]["dp"], trainer["mesh"]["mp"]
        # the axis names HybridCommunicateGroup gives its mesh
        mesh = Mesh(np.array(topo.devices[:dp * mp]).reshape(dp, 1, 1, 1, mp),
                    ("dp", "pp", "sharding", "sep", "mp"))
        specs = llama.param_specs(cfg, mp_axis="mp")
        place = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        data = NamedSharding(mesh, llama.batch_spec(("dp", "sharding")))
        scalar = NamedSharding(mesh, PartitionSpec())
        under = functools.partial(jax.set_mesh, mesh)
    else:
        one = SingleDeviceSharding(topo.devices[0])
        place = jax.tree_util.tree_map(lambda _: one, shapes)
        data = scalar = one
    params = jax.tree_util.tree_map(
        lambda a, w: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=w),
        shapes, place)
    moments = jax.tree_util.tree_map(
        lambda a, w: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=w),
        shapes, place)
    opt = {"m": moments, "v": moments,
           "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar)}
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
    _, step_fn = llama.make_train_step(cfg, lr=float(trainer["lr"]))
    with under():
        compiled = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
            params, opt, ids, ids).compile()
    row = report(f"train step batch={batch} seq={seq}", compiled)
    text = compiled.as_text()
    print(json.dumps({"per_device": True, "batch": batch,
                      "collectives": {k: text.count(k + "(") + text.count(
                          k + "-start(") for k in (
                          "all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")},
                      "tpu_custom_calls": text.count("tpu_custom_call"),
                      "fits_16gb": row["total_gb"] < 15.75}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--num-blocks", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import traffic
    from paddle_tpu.jit import train_step
    from paddle_tpu.kernels import dispatch
    # the two places the program asks which backend it is on: steer both
    # to the chip's answer here, in the script, not through an option
    dispatch.on_tpu = lambda: True        # kernels lower natively
    train_step.donation_supported = lambda: True    # buffers are donated
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    print(json.dumps({"cell": cell["name"], "compiled_for": "v5e:2x2 "
                      "(described, not attached)",
                      "device_kind": topo.devices[0].device_kind}))
    jax.config.update("jax_enable_compilation_cache", False)
    if mix["kind"] == "serve":
        serve(config, mix, SingleDeviceSharding(topo.devices[0]),
              args.num_blocks)
    else:
        train(config, mix, topo, args.batch)


if __name__ == "__main__":
    main()
