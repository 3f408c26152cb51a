"""openPangu-Ultra-MoE in plain float32 ``jax.numpy``: the reference the
benchmark holds paddle_tpu to. One sequence at a time, the EXPANDED form of
the attention (per-head keys and values made from the compressed vector),
no kernels, no cache, no batching, no sorting of tokens by expert. It
imports nothing from ``paddle_tpu`` and never takes the program's routing
decisions: it scores, picks and weighs the experts itself.

The equations (``N_*`` an RMSNorm with its own weight, ``eps`` 1e-5)::

    y = MLA(N_in(x));        x = x + N_post_attn(y)
    f = FFN_l(N_pre_mlp(x)); x = x + N_post_mlp(f)

    MLA(a):  c_q = N_q(a W_qa);  [q_nope | q_rope]_h = c_q W_qb
             [c_kv | k_rope] = a W_kva;  c_kv = N_kv(c_kv)
             rotary embedding on interleaved pairs of q_rope, k_rope
             k_h = [c_kv W_kb,h^K | k_rope];  v_h = c_kv W_kb,h^V
             p = causal softmax(q_h . k_h / sqrt(d_nope + d_rope))
             concat_h(p v_h) W_o
    FFN of a leading dense layer: (silu(m W_1) * m W_3) W_2
    FFN of an expert layer: s = sigmoid(m W_g) over ALL experts; top-k by
             s; w = factor * s_sel / (sum s_sel + 1e-20);
             Shared(m) + sum over the picked experts HELD HERE of w_e E_e(m)

What the experts held on other chips would add is left out, as the program
leaves it out (the configuration's ``deployment``): the router keeps its
published width, and expert ``e`` of the weights is expert ``expert_offset
+ e`` of the router.

As the source's modeling file is remembered (no network here; the
configuration file lists each under ``assumed``): the sandwich placement
of the four norms, sigmoid scores with no group limit and no bias term,
interleaved-pair rotary embedding, scores over 192 lanes and values over
128.

The walk over the PROGRAM's parameter tree (``embed``, ``n_blocks``,
``block``, ``head``) is the last section: ``params["runs"]`` is a list of
runs of like layers, each a tree stacked over its layers, dense or expert
by whether it has a ``router``. ``block`` upcasts one block's weights
alone, and of a block's routed experts one at a time.

**Where the reference has no one answer.** The top-k over the router's
scores is a step: where a HELD expert's score lies within the rounding of
the configuration's stated precision of the edge of the top-k, the
float32 function and a sound program of that precision may stand on
different sides of the step, and the whole of that expert's part of the
token's routed sum comes or goes. The walk therefore carries, beside the
residual stream, each position's least :func:`routing_margin` over the
expert layers so far, and ``head`` gives a position whose margin is under
the configuration's ``oracle.tie_margin`` a FLAT row of logits: every
token is its maximum there, so whatever is compared by logit reads zero
and the position is not judged. The margin comes from the reference's own
float32 scores alone; nothing of the program's is seen. Every other
position is judged at a tolerance the stated precision sets, not at what
one expert's part moves a token by. ``forward`` returns the plain logits.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_GROUP = 16        # heads attended at once: [16, S, S] scores at a time


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """``x [S, .., D]`` at positions 0..S-1: pairs ``(2i, 2i+1)`` rotate by
    ``pos * theta ** (-2i / D)``."""
    S, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    x0, x1 = x[..., 0::2], x[..., 1::2]
    y = jnp.stack([x0 * jnp.cos(ang) - x1 * jnp.sin(ang),
                   x0 * jnp.sin(ang) + x1 * jnp.cos(ang)], axis=-1)
    return y.reshape(x.shape)


def attention(a, lp, cfg):
    """``a [S, E]`` (normed) -> ``[S, E]``; ``lp`` float32."""
    S = a.shape[0]
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    R, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    q = (rms_norm(a @ lp["wq_a"], lp["ln_q"], eps) @ lp["wq_b"]).reshape(
        S, H, dn + dr)
    kv = a @ lp["wkv_a"]
    c = rms_norm(kv[:, :R], lp["ln_kv"], eps)
    k_rope = rotary(kv[:, R:], theta)                       # [S, dr]
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], theta)
    wkb = lp["wkv_b"]                                       # [R, H, dn + dv]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def heads(g):
        """``HEAD_GROUP`` heads from ``g`` on: per-head keys and values."""
        sl = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=g,
                               slice_size=min(HEAD_GROUP, H))
        w = sl(wkb, axis=1)
        k = jnp.einsum("sr,rhd->shd", c, w[..., :dn])
        v = jnp.einsum("sr,rhd->shd", c, w[..., dn:])
        s = (jnp.einsum("qhd,khd->hqk", sl(q_nope, axis=1), k) +
             jnp.einsum("qhd,kd->hqk", sl(q_rope, axis=1), k_rope))
        s = jnp.where(causal[None], s / jnp.sqrt(F32(dn + dr)), -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    starts = jnp.arange(0, H, min(HEAD_GROUP, H))
    o = jax.lax.map(heads, starts)                          # [G, S, hg, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(S, -1)
    return o @ lp["wo"]


def gated_ffn(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def expert_ffn(m, lp, cfg):
    """The expert layer's FFN on ``m [S, E]``: the shared expert plus the
    held experts' part of the routed sum. ``lp["w_gu"]``/``lp["w_down"]``
    arrive in the type they are stored in and are upcast one expert at a
    time."""
    k = cfg["num_experts_per_tok"]
    first = int(cfg.get("expert_offset", 0))
    s = jax.nn.sigmoid(m @ lp["router"])                    # [S, all]
    top, ids = jax.lax.top_k(s, k)
    w = cfg["routed_scaling_factor"] * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    out = gated_ffn(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    I = lp["w_down"].shape[1]

    def one(e, out):
        gu = jnp.asarray(lp["w_gu"][e], F32)
        down = jnp.asarray(lp["w_down"][e], F32)
        weight = jnp.where(ids == first + e, w, 0.0).sum(-1)  # [S]
        y = gated_ffn(m, gu[:, :I], gu[:, I:], down)
        return out + weight[:, None] * y

    return jax.lax.fori_loop(0, lp["w_gu"].shape[0], one, out)


def routing_margin(m, lp, cfg):
    """``[S]``: how far the nearest HELD expert's router logit lies from
    the edge of the top-k, in standard deviations of the token's logits
    over all experts. For a held expert among the picks the edge is the
    best logit left out (what would take its place), for one left out the
    weakest pick (what it would displace). The scores are a sigmoid of the
    logits, so the logits order the picks as the scores do."""
    k = cfg["num_experts_per_tok"]
    first = int(cfg.get("expert_offset", 0))
    z = m @ lp["router"]                                    # [S, all]
    top = jax.lax.top_k(z, k + 1)[0]
    weakest_in, best_out = top[:, k - 1:k], top[:, k:]
    held = z[:, first:first + lp["w_gu"].shape[0]]
    edge = jnp.where(held >= weakest_in, held - best_out, weakest_in - held)
    return edge.min(-1) / z.std(-1)


def decoder_block(x, lp, cfg, margin=None):
    """One block on ``x [S, E]``; ``lp`` is ONE layer's weights (an expert
    layer's routed experts still stacked, in their stored type). With
    ``margin [S]`` it returns ``(x, margin)``, the margin lowered to this
    layer's :func:`routing_margin` where that is less."""
    with jax.default_matmul_precision("highest"):
        routed = {k: lp[k] for k in ("w_gu",) if k in lp}
        if routed:
            routed["w_down"] = lp["w_down"]
        lp = {k: jnp.asarray(v, F32) for k, v in lp.items()
              if k not in routed}
        lp.update(routed)
        x = jnp.asarray(x, F32)
        eps = cfg["rms_norm_eps"]
        y = attention(rms_norm(x, lp["ln_in"], eps), lp, cfg)
        x = x + rms_norm(y, lp["ln_post_attn"], eps)
        m = rms_norm(x, lp["ln_pre_mlp"], eps)
        if "router" in lp:
            f = expert_ffn(m, lp, cfg)
            if margin is not None:
                margin = jnp.minimum(margin, routing_margin(m, lp, cfg))
        else:
            f = gated_ffn(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = x + rms_norm(f, lp["ln_post_mlp"], eps)
        return x if margin is None else (x, margin)


# ---------------------------------------------------------------------------
# over the program's parameter tree
# ---------------------------------------------------------------------------

def embed(params: Dict[str, Any], ids):
    """``ids [S]`` -> what the walk carries: ``{"x": [S, E] float32,
    "margin": [S]}``, no expert layer seen yet."""
    return {"x": jnp.asarray(params["embed"][ids], F32),
            "margin": jnp.full(ids.shape, jnp.inf, F32)}


def n_blocks(params: Dict[str, Any]) -> int:
    return sum(run["ln_in"].shape[0] for run in params["runs"])


def _locate(params, i: int):
    for run in params["runs"]:
        n = run["ln_in"].shape[0]
        if i < n:
            return run, i
        i -= n
    raise IndexError("no such block")


WIDTHS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
          "kv_lora_rank", "rms_norm_eps", "rope_theta",
          "num_experts_per_tok", "routed_scaling_factor", "expert_offset")


@functools.partial(jax.jit, static_argnums=(3,))
def _stacked_block(state, run, i, widths):
    layer = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
             for k, v in run.items()}
    x, margin = decoder_block(state["x"], layer, dict(widths),
                              state["margin"])
    return {"x": x, "margin": margin}


def block(params: Dict[str, Any], i: int, state, cfg: Dict[str, Any]):
    """Block ``i`` on what ``embed`` or the block before it returned: one
    compiled program a run of like layers, which slices layer ``i`` out of
    the run's stacked weights and upcasts that layer alone (its routed
    experts one at a time), so the reference fits beside the served
    weights."""
    run, j = _locate(params, i)
    widths = tuple((k, cfg.get(k, 0)) for k in WIDTHS)
    return _stacked_block(state, run, jnp.int32(j), widths)


def logits(params: Dict[str, Any], x, cfg: Dict[str, Any]):
    """``x [S, E] -> logits [S, V]``: final norm and output head."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(jnp.asarray(x, F32), jnp.asarray(params["ln_f"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)


def head(params: Dict[str, Any], state, cfg: Dict[str, Any]):
    """The walk's end: ``logits [S, V]``, flat (all zero) at the positions
    the reference does not judge (the module's docstring): those whose
    margin is under the configuration's ``oracle.tie_margin``; without
    that key every position is judged."""
    tie = float(cfg.get("oracle", {}).get("tie_margin", 0.0))
    judged = state["margin"] >= tie
    return jnp.where(judged[:, None], logits(params, state["x"], cfg), 0.0)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any]):
    """``ids [S] -> logits [S, V]`` for one sequence, every position."""
    state = embed(params, ids)
    for i in range(n_blocks(params)):
        state = block(params, i, state, cfg)
    return logits(params, state["x"], cfg)
