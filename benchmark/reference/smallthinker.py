"""SmallThinker in plain float32 ``jax.numpy``: the reference the benchmark
holds paddle_tpu to. One sequence at a time, no kernels, no cache, no
batching, no sorting of tokens by expert; the window and the causal edge
as ONE explicit mask, computed in blocks of queries so that a sequence of
8k tokens fits beside the served weights. It imports nothing from
``paddle_tpu`` and nothing of the other references, and never takes the
program's routing decisions: it scores, picks and weighs the experts
itself.

The equations (``N_*`` an RMSNorm with its own weight, ``eps``
``rms_norm_eps``; ``l`` the layer)::

    r   = x W_r                       (router on the layer's INPUT)
    ids = top-k(r);  w = softmax(r[ids])
    a   = N_in(x);  q = a W_q as H heads of D; k = a W_k, v = a W_v as Hk
          heads each; no bias, no normalisation of queries or keys
    window layer (sliding_window_layout[l] = 1): rotary embedding, theta
          rope_theta, on rotate-half pairs (i, i + D/2) of all D lanes;
          j in (i - sliding_window_size, i]
    full layer   (sliding_window_layout[l] = 0): no rotary (NoPE); j <= i
    o_h = softmax(q_h k_{h // (H/Hk)}^T / sqrt(D)) v_{h // (H/Hk)}
    x   = x + concat_h(o_h) W_o
    m   = N_post(x);  x = x + sum_{e in ids} w_e (relu(m W_gate,e) *
          m W_up,e) W_down,e
    logits = N_f(x) W_head            (untied)

What the config's keys do not settle (the configuration file lists each
under ``assumed``, with the alternative): the router's input, the ReGLU,
the pre-norm placement with no q/k norm, rotate-half pairs.

The walk over the PROGRAM's parameter tree (``embed``, ``n_blocks``,
``block``, ``head``) is the last section: ``params["first"]`` is layer 0,
``params["periods"]`` holds ``"slide" [P, S, ..]`` and ``"full" [P, ..]``
(a period is its ``S`` window layers, then its full one) and
``params["tail"]`` the window layers after the last whole period. The
keys and values are ``wkv``, ``[W_k | W_v]`` side by side, and each
expert's ``w_gu`` is ``[W_gate | W_up]``. ``block`` upcasts one block's
weights alone, and of its experts one at a time.

**Where the reference has no one answer.** The top-k over the router's
logits is a step: where the k-th and the (k+1)-th logit of a position lie
within the rounding of the configuration's stated precision, the float32
function and a sound program of that precision may pick differently. The
walk therefore carries, beside the residual stream, each position's least
:func:`routing_margin` over the layers so far, and ``head`` gives a
position whose margin is under the configuration's ``oracle.tie_margin`` a
FLAT row of logits, which is not judged. ``forward`` returns the plain
logits.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256          # query positions a block of the attention


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta: float):
    """``x [S, H, D]`` at positions 0..S-1, pairs ``(i, i + D/2)`` rotated
    by ``pos * theta^(-2i/D)``."""
    S, D = x.shape[0], x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(D // 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a, lp, cfg):
    """``a [S, E]`` (normed) -> ``[S, E]``; ``lp`` float32; ``cfg`` holds
    this layer's ``window`` (0: a full layer) and the widths."""
    S = a.shape[0]
    D, Hk, H = cfg["head_dim"], cfg["num_key_value_heads"], \
        cfg["num_attention_heads"]
    G = H // Hk
    q = (a @ lp["wq"]).reshape(S, Hk, G, D)
    kv = (a @ lp["wkv"]).reshape(S, 2 * Hk, D)
    k, v = kv[:, :Hk], kv[:, Hk:]
    if cfg["window"]:
        q = rotary(q.reshape(S, H, D), cfg["rope_theta"]).reshape(S, Hk, G, D)
        k = rotary(k, cfg["rope_theta"])
    B = min(S, Q_BLOCK)
    n = -(-S // B)
    q = jnp.pad(q, ((0, n * B - S), (0, 0), (0, 0), (0, 0)))
    j = jnp.arange(S)[None, :]

    def one(b):
        """Queries ``b * B ...`` against every key, masked."""
        qb = jax.lax.dynamic_slice_in_dim(q, b * B, B)        # [B, Hk, G, D]
        i = b * B + jnp.arange(B)[:, None]
        mask = j <= i
        if cfg["window"]:
            mask = mask & (j > i - cfg["window"])
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    o = jax.lax.map(one, jnp.arange(n)).reshape(n * B, H * D)[:S]
    return o @ lp["wo"]


def route(x, lp, cfg):
    """``(ids [S, k], weights [S, k])``: the top-k of the router's logits
    on ``x`` and a softmax over them."""
    top, ids = jax.lax.top_k(x @ lp["router"], cfg["k"])
    return ids, jax.nn.softmax(top, -1)


def expert_ffn(m, ids, w, lp):
    """``sum_{e in ids} w_e E_e(m)`` on ``m [S, E]``; ``lp["w_gu"]`` and
    ``lp["w_down"]`` arrive in their stored type and are upcast one expert
    at a time."""
    I = lp["w_down"].shape[1]

    def one(e, out):
        gu = jnp.asarray(lp["w_gu"][e], F32)
        down = jnp.asarray(lp["w_down"][e], F32)
        weight = jnp.where(ids == e, w, 0.0).sum(-1)             # [S]
        y = (jax.nn.relu(m @ gu[:, :I]) * (m @ gu[:, I:])) @ down
        return out + weight[:, None] * y

    return jax.lax.fori_loop(0, lp["w_gu"].shape[0], one,
                             jnp.zeros_like(m))


def routing_margin(x, lp, cfg):
    """``[S]``: the distance between the k-th and the (k+1)-th router
    logit, in standard deviations of the position's logits over all
    experts (every expert is held, so either side of that edge can
    flip)."""
    z = x @ lp["router"]
    top = jax.lax.top_k(z, cfg["k"] + 1)[0]
    return (top[:, -2] - top[:, -1]) / z.std(-1)


def decoder_block(x, lp, cfg, margin=None):
    """One block on ``x [S, E]``; ``lp`` is ONE layer's weights (its
    experts still stacked, in their stored type). With ``margin [S]`` it
    returns ``(x, margin)``, the margin lowered to this layer's
    :func:`routing_margin` where that is less."""
    with jax.default_matmul_precision("highest"):
        experts = {k: lp[k] for k in ("w_gu", "w_down")}
        lp = {k: jnp.asarray(v, F32) for k, v in lp.items()
              if k not in experts}
        lp.update(experts)
        x = jnp.asarray(x, F32)
        eps = cfg["rms_norm_eps"]
        ids, w = route(x, lp, cfg)
        if margin is not None:
            margin = jnp.minimum(margin, routing_margin(x, lp, cfg))
        x = x + attention(rms_norm(x, lp["ln_in"], eps), lp, cfg)
        x = x + expert_ffn(rms_norm(x, lp["ln_post"], eps), ids, w, lp)
        return x if margin is None else (x, margin)


# ---------------------------------------------------------------------------
# over the program's parameter tree
# ---------------------------------------------------------------------------

def embed(params: Dict[str, Any], ids):
    """``ids [S]`` -> what the walk carries: ``{"x": [S, E] float32,
    "margin": [S]}``, no layer seen yet."""
    return {"x": jnp.asarray(params["embed"][ids], F32),
            "margin": jnp.full(ids.shape, jnp.inf, F32)}


def _stack(params):
    """``(P, S, T)``: whole periods, window layers a period, tail."""
    P, S = (params["periods"]["slide"]["ln_in"].shape[:2]
            if "periods" in params else (0, 0))
    T = params["tail"]["ln_in"].shape[0] if "tail" in params else 0
    return P, S, T


def n_blocks(params: Dict[str, Any]) -> int:
    P, S, T = _stack(params)
    return 1 + P * (S + 1) + T


def _locate(params, i: int):
    """``(stacked tree, index into its leading axes)`` of block ``i``."""
    P, S, T = _stack(params)
    if i == 0:
        return params["first"], ()
    p, r = divmod(i - 1, S + 1)
    if p < P:
        return ((params["periods"]["slide"], (p, r)) if r < S
                else (params["periods"]["full"], (p,)))
    t = i - 1 - P * (S + 1)
    if t >= T:
        raise IndexError("no such block")
    return params["tail"], (t,)


def _widths(cfg: Dict[str, Any], i: int):
    """The scalars block ``i`` needs, hashable: its window (0: a full
    layer) and the widths every block shares."""
    window = int(cfg["sliding_window_layout"][i]) * int(
        cfg["sliding_window_size"])
    out = {k: cfg[k] for k in ("head_dim", "num_key_value_heads",
                               "num_attention_heads", "rms_norm_eps",
                               "rope_theta")}
    out.update(window=window, k=int(cfg["moe_num_active_primary_experts"]))
    return tuple(sorted(out.items()))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _stacked_block(state, tree, at, n_at, widths):
    layer = tree
    for d in range(n_at):
        layer = {k: jax.lax.dynamic_index_in_dim(v, at[d], 0, keepdims=False)
                 for k, v in layer.items()}
    x, margin = decoder_block(state["x"], layer, dict(widths),
                              state["margin"])
    return {"x": x, "margin": margin}


def block(params: Dict[str, Any], i: int, state, cfg: Dict[str, Any]):
    """Block ``i`` on what ``embed`` or the block before it returned: one
    compiled program a kind of block, which slices the layer out of its
    stacked weights and upcasts that layer alone (its experts one at a
    time), so the reference fits beside the served weights."""
    tree, at = _locate(params, i)
    return _stacked_block(state, tree, jnp.asarray(at, jnp.int32).reshape(-1),
                          len(at), _widths(cfg, i))


def logits(params: Dict[str, Any], x, cfg: Dict[str, Any]):
    """``x [S, E] -> logits [S, V]``: final norm and output head."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(jnp.asarray(x, F32), jnp.asarray(params["ln_f"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)


def head(params: Dict[str, Any], state, cfg: Dict[str, Any]):
    """The walk's end: ``logits [S, V]``, flat (all zero) at the positions
    the reference does not judge: those whose margin is under the
    configuration's ``oracle.tie_margin``; without that key every position
    is judged. A position is flattened by zeroing its row BEFORE the head
    (the norm of a zero row is zero), so that no second ``[S, V]`` array
    is made."""
    tie = float(cfg.get("oracle", {}).get("tie_margin", 0.0))
    judged = state["margin"] >= tie
    return logits(params, jnp.where(judged[:, None], state["x"], 0.0), cfg)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any]):
    """``ids [S] -> logits [S, V]`` for one sequence, every position."""
    state = embed(params, ids)
    for i in range(n_blocks(params)):
        state = block(params, i, state, cfg)
    return logits(params, state["x"], cfg)
