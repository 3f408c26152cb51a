"""KV-cache text generation with the flagship LLaMA model.

Greedy / top-p decoding where prefill + the whole decode loop is ONE
compiled XLA program, plus the streaming token-at-a-time session
(donated-cache) used by serving.

    PYTHONPATH=. python examples/generate_llama.py --max-new 32 --top-p 0.9
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit import enable_compile_cache
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import DecodeSession
    enable_compile_cache()

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=1024, hidden_size=256,
                      intermediate_size=688, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=4)
    model = LlamaForCausalLM(cfg, key=jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, 16)).astype(np.int32)

    # one-program batch generation (jit-cached by shape + sampling knobs)
    out = model.generate(
        paddle.to_tensor(prompts), max_new_tokens=args.max_new,
        temperature=args.temperature, top_p=args.top_p)
    print("batch generate:", np.asarray(out._value)[:, :12], "...")

    # streaming session: one token per dispatch, cache donated in place
    sess = DecodeSession(model.params_pytree(), cfg,
                         capacity=16 + args.max_new)
    logits = sess.prefill(prompts)
    stream = []
    for _ in range(8):
        tok = np.asarray(logits._value if hasattr(logits, "_value")
                         else logits).argmax(-1).astype(np.int32)
        stream.append(tok)
        logits = sess.step(tok)
    print("streamed first 8:", np.stack(stream, 1))


if __name__ == "__main__":
    main()
