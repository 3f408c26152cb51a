"""Pretrain LLaMA through the COMPILED pipeline schedule.

The whole step — vocab-parallel embedding + LM head over the ``pp`` axis,
the interleaved circular schedule for the decoder blocks
(``--virtual_pp``), micro-batch loop, backward, AdamW — is ONE XLA program
(``llama.make_pp_train_step``). Run on the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=. python examples/train_llama_pp.py --dp 2 --pp 4 \\
        --virtual_pp 2
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--virtual_pp", type=int, default=2,
                    help="circular repeats (interleaved 1F1B)")
    ap.add_argument("--micro_batches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from paddle_tpu.distributed.pipeline import pipeline_ticks
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.jit import enable_compile_cache
    from paddle_tpu.models import llama
    enable_compile_cache()

    S, V, M = args.pp, args.virtual_pp, args.micro_batches
    cfg = llama.LlamaConfig(
        vocab_size=4096, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2 * S * V, num_attention_heads=4,
        num_key_value_heads=4, use_kernels=False)
    devices = jax.devices()[: args.dp * S]
    mesh = build_mesh({"dp": args.dp, "pp": S}, devices)

    params = llama.to_pp_layout(
        llama.init_params(cfg, jax.random.PRNGKey(0)), S, V)
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, llama.pp_param_specs(cfg))
    init_opt, step = llama.make_pp_train_step(
        cfg, mesh, micro_batches=M, circular_repeats=V, lr=3e-4)
    opt = jax.device_put(init_opt(params))
    jstep = jax.jit(step)

    ticks = pipeline_ticks(M, S, V)
    print(f"stages={S} virtual={V} micro_batches={M}: {ticks} chunk-ticks "
          f"per step (bubble {(S - 1) / V / (M + (S - 1) / V):.1%})")

    B = M * args.dp
    rng = np.random.default_rng(0)
    for i in range(args.steps):
        ids = rng.integers(0, cfg.vocab_size, (B, args.seq)).astype(np.int32)
        params, opt, loss = jstep(params, opt, jnp.asarray(ids),
                                  jnp.asarray(ids))
        print(f"step {i:3d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
