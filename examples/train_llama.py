"""Pretrain a small LLaMA-family decoder end to end.

From the repo root (the package is not installed, so it rides
``PYTHONPATH``). On one TPU chip as-is; on a four-chip host with
``--dp 2 --mp 2`` (one process drives all four); on the virtual CPU mesh
only where the environment says so:

    PYTHONPATH=. python examples/train_llama.py --steps 20
    PYTHONPATH=. python examples/train_llama.py --dp 2 --mp 2 --steps 5
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=. python examples/train_llama.py --dp 2 --mp 2 --fsdp 2

The persistent compile cache is ``JAX_COMPILATION_CACHE_DIR`` where set,
else ``<checkout>/.jax_cache`` (``paddle_tpu.jit.enable_compile_cache``).
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--sep", type=int, default=1, help="ring-attention CP")
    args = ap.parse_args()

    from paddle_tpu.jit import enable_compile_cache
    from paddle_tpu.models import llama
    from paddle_tpu.distributed.topology import (HybridCommunicateGroup,
                                                 set_hybrid_communicate_group)
    from jax.sharding import NamedSharding
    enable_compile_cache()

    cfg = llama.LlamaConfig(
        vocab_size=4096, hidden_size=args.hidden,
        intermediate_size=args.hidden * 11 // 4 // 8 * 8 or 64,
        num_hidden_layers=args.layers,
        num_attention_heads=max(4, args.hidden // 64),
        use_kernels=jax.default_backend() == "tpu",
        remat=True, dtype=jnp.bfloat16,
        sep_axis="sep" if args.sep > 1 else None)
    print(f"model: {llama.num_params(cfg) / 1e6:.1f}M params, "
          f"backend={jax.default_backend()}")

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    hcg = HybridCommunicateGroup(dp=args.dp, mp=args.mp, sharding=args.fsdp,
                                 sep=args.sep,
                                 devices=jax.devices()[: args.dp * args.mp
                                                       * args.fsdp * args.sep])
    set_hybrid_communicate_group(hcg)
    params = llama.shard_params(
        params, hcg.mesh, cfg,
        mp_axis="mp" if args.mp > 1 else None,
        fsdp_axis="sharding" if args.fsdp > 1 else None)

    init_opt, train_step = llama.make_train_step(cfg, lr=3e-4)
    opt = jax.device_put(init_opt(params))
    batch_sharding = NamedSharding(
        hcg.mesh, llama.batch_spec(("dp", "sharding"),
                                   "sep" if args.sep > 1 else None))
    rng = np.random.default_rng(0)
    jstep = jax.jit(train_step, donate_argnums=(0, 1))

    t0 = time.time()
    for step in range(args.steps):
        ids = jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     (args.batch, args.seq)), jnp.int32),
            batch_sharding)
        # under the mesh the flash kernel runs as a per-shard region
        # (GSPMD cannot partition a Mosaic kernel)
        with jax.set_mesh(hcg.mesh):
            params, opt, loss = jstep(params, opt, ids, ids)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"({(time.time() - t0):.1f}s)")
    tok_s = args.steps * args.batch * args.seq / (time.time() - t0)
    print(f"done: {tok_s:,.0f} tokens/s (incl. compile)")


if __name__ == "__main__":
    main()
