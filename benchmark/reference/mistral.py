"""Mistral-7B (v0.1-v0.3) in plain float32 ``jax.numpy``: the reference the
benchmark holds paddle_tpu to.

It follows ``MistralForCausalLM`` as published (huggingface.co/mistralai/
Mistral-7B-v0.3, ``modeling_mistral.py``): pre-norm decoder layers with
RMSNorm, grouped-query attention with rotary embeddings in the
rotate-half convention, a SwiGLU feed-forward, no biases, a final RMSNorm
and an untied output head. v0.3 has no sliding window, so attention is
plainly causal. One sequence at a time: no kernels, no cache, no batching.
It imports nothing from ``paddle_tpu``.

Weights are ``[in, out]`` matrices (``x @ w``) in a dict of its own layout::

    {"embed_tokens": [V, E], "norm": [E], "lm_head": [E, V],
     "layers": [{"input_layernorm": [E], "q_proj": [E, H*D],
                 "k_proj": [E, Hk*D], "v_proj": [E, Hk*D],
                 "o_proj": [H*D, E], "post_attention_layernorm": [E],
                 "gate_proj": [E, I], "up_proj": [E, I],
                 "down_proj": [I, E]}, ...]}

Every entry point upcasts what it is given to float32 and runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """``x [S, heads, D]`` at positions 0..S-1, rotate-half convention."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def decoder_layer(x, lp: Dict[str, Any], cfg: Dict[str, Any]):
    """One layer on ``x [S, E]`` (float32 in, float32 out)."""
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        x = jnp.asarray(x, F32)
        S = x.shape[0]
        H = cfg["num_attention_heads"]
        Hk = cfg["num_key_value_heads"]
        D = cfg.get("head_dim") or cfg["hidden_size"] // H
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        h = rms_norm(x, lp["input_layernorm"], eps)
        q = rotary((h @ lp["q_proj"]).reshape(S, H, D), theta)
        k = rotary((h @ lp["k_proj"]).reshape(S, Hk, D), theta)
        v = (h @ lp["v_proj"]).reshape(S, Hk, D)
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(D))
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(S, H * D) @ lp["o_proj"]
        h = rms_norm(x, lp["post_attention_layernorm"], eps)
        g = jax.nn.silu(h @ lp["gate_proj"]) * (h @ lp["up_proj"])
        return x + g @ lp["down_proj"]


def embed(ids, table):
    return jnp.asarray(table[ids], F32)


def head(x, norm_w, lm_head, cfg: Dict[str, Any]):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(jnp.asarray(x, F32), jnp.asarray(norm_w, F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(lm_head, F32)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any]):
    """``ids [S] -> logits [S, V]`` for one sequence."""
    x = embed(ids, params["embed_tokens"])
    for lp in params["layers"]:
        x = decoder_layer(x, lp, cfg)
    return head(x, params["norm"], params["lm_head"], cfg)


def loss(params: Dict[str, Any], ids, labels, cfg: Dict[str, Any]):
    """Mean cross-entropy over ``ids [B, S]`` against ``labels [B, S]``
    (the token each position has to predict; negative = ignored)."""
    tot, cnt = F32(0.0), F32(0.0)
    for row, lab in zip(ids, labels):
        logits = forward(params, row, cfg)
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[:, None],
                                  -1)[:, 0]
        m = lab >= 0
        tot = tot + jnp.where(m, lse - tgt, 0.0).sum()
        cnt = cnt + m.sum()
    return tot / jnp.maximum(cnt, 1.0)


def loss_and_grad_norm(params: Dict[str, Any], ids, labels,
                       cfg: Dict[str, Any]):
    """The loss and the global L2 norm of its gradient over every
    parameter."""
    value, grads = jax.value_and_grad(loss)(_f32(params), ids, labels, cfg)
    sq = sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
    return value, jnp.sqrt(sq)


def from_stacked(params: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """One layer of the reference layout out of a pytree that stacks each
    weight over a leading layer axis under paddle_tpu's names. The map of
    names is the whole coupling between the two layouts."""
    names = {"input_layernorm": "ln_attn", "q_proj": "wq", "k_proj": "wk",
             "v_proj": "wv", "o_proj": "wo",
             "post_attention_layernorm": "ln_mlp", "gate_proj": "w_gate",
             "up_proj": "w_up", "down_proj": "w_down"}
    return {ours: params["layers"][theirs][layer]
            for ours, theirs in names.items()}


def from_program(params: Dict[str, Any]) -> Dict[str, Any]:
    n = params["layers"]["wq"].shape[0]
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [from_stacked(params, i) for i in range(n)]}
