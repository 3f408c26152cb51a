"""Pretrain a LLaMA-MoE (Mixtral-style) decoder with expert parallelism.

Experts are GShard-routed; their stacked weights are sharded E/ep per
device over the ``ep`` mesh axis while the batch is data-parallel over
``dp`` — GSPMD inserts the expert all_to_all. From the repo root, on one
TPU chip as-is (``--dp 1 --ep 1``), on a four-chip host with
``--dp 2 --ep 2``, or on the virtual CPU mesh where the environment says so:

    PYTHONPATH=. python examples/train_llama_moe.py --steps 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=. python examples/train_llama_moe.py --dp 2 --ep 4
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.jit import enable_compile_cache
    enable_compile_cache()
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=4096, hidden_size=256, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        use_kernels=jax.default_backend() == "tpu",
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        moe_num_experts=args.experts, moe_top_k=2,
        ep_axis="ep" if args.ep > 1 else None)

    devices = jax.devices()[: args.dp * args.ep]
    mesh = build_mesh({"dp": args.dp, "ep": args.ep}, devices)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, llama.param_specs(cfg, mp_axis=None))
    print(f"params: {llama.num_params(cfg):,} "
          f"({args.experts} experts, E/ep = {args.experts // args.ep} "
          f"per device)")

    init_opt, step = llama.make_train_step(cfg, lr=3e-4)
    opt = jax.device_put(init_opt(params))
    jstep = jax.jit(step, donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    bs = NamedSharding(mesh, llama.batch_spec(("dp",)))
    for i in range(args.steps):
        ids = jax.device_put(
            rng.integers(0, cfg.vocab_size,
                         (args.batch, args.seq)).astype(np.int32), bs)
        # under the mesh the flash kernel runs as a per-shard region
        with jax.set_mesh(mesh):
            params, opt, loss = jstep(params, opt, ids, ids)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
