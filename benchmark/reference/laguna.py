"""Laguna-S in plain float32 ``jax.numpy``: the reference the benchmark
holds paddle_tpu to. One sequence at a time, no kernels, no cache, no
batching, no sorting of tokens by expert, the window and the causal edge
as ONE explicit mask over the whole sequence. It imports nothing from
``paddle_tpu`` and nothing of the other references, and never takes the
program's routing decisions: it scores, picks and weighs the experts
itself.

The equations (``N_*`` an RMSNorm with its own weight, ``eps`` 1e-6; ``l``
the layer, ``H_l`` = ``num_attention_heads_per_layer[l]``, kind =
``layer_types[l]``)::

    a = N_in(x);    x = x + Attn_l(a)
    m = N_post(x);  x = x + FFN_l(m)

    Attn_l(a): q = a W_q as H_l heads of 128; [k | v] = a W_kv as 8 heads
             each; rotary embedding by kind on rotate-half pairs (i, i +
             r/2) of the first r lanes, the other lanes passing through:
               sliding: r = 128, theta 1e4, plain
               full:    r = 64, theta 5e5, YaRN (``yarn_inv_freq``), cosine
                        and sine times ``attention_factor``
             query head h reads key-value head h // (H_l / 8)
             p = softmax(q_h . k / sqrt(128)) over j <= i and, in a
                 sliding layer, j > i - sliding_window
             z = sigmoid(a W_g)  (W_g [E, H_l]);  concat_h(z_h p v) W_o
    FFN of layer 0: (silu(m W_1) * m W_3) W_2
    FFN of every other layer: s = sigmoid(m W_r) over ALL experts; top-k
             by s; w = factor * s_sel / sum s_sel;
             Shared(m) + sum over the picked experts HELD HERE of w_e E_e(m)

What the experts held on other chips would add is left out, as the program
leaves it out (the configuration's ``deployment``): the router keeps its
published width, and expert ``e`` of the weights is expert ``expert_offset
+ e`` of the router.

What the config's keys do not settle (the configuration file lists each
under ``assumed``, with the alternative): the pre-norm placement, no
normalisation of queries or keys, rotate-half pairs, the gate's form,
``silu``, sigmoid scores with no bias term and no group limit, the shared
expert added ungated.

The walk over the PROGRAM's parameter tree (``embed``, ``n_blocks``,
``block``, ``head``) is the last section: ``params["first"]`` is layer 0,
``params["periods"]`` holds ``"slide" [P, S, ..]`` and ``"full" [P, ..]``
(a period is its ``S`` sliding layers, then its full one) and
``params["tail"]`` the sliding layers after the last whole period.
``block`` upcasts one block's weights alone, and of its routed experts one
at a time; attention runs one key-value head's query heads at a time.

**Where the reference has no one answer.** The top-k over the router's
scores is a step: where a HELD expert's score lies within the rounding of
the configuration's stated precision of the edge of the top-k, the float32
function and a sound program of that precision may stand on different
sides of the step. The walk therefore carries, beside the residual stream,
each position's least :func:`routing_margin` over the expert layers so far,
and ``head`` gives a position whose margin is under the configuration's
``oracle.tie_margin`` a FLAT row of logits, which is not judged. The margin
comes from the reference's own float32 scores alone. ``forward`` returns
the plain logits.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(r: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """``[r / 2]`` frequencies as ``transformers`` computes YaRN: ``extra =
    theta^(-2i/r)``, ``inter = extra / factor``; ``dim(n) = r ln(original
    / (2 pi n)) / (2 ln theta)``; ``low = floor(dim(beta_fast))``, ``high
    = ceil(dim(beta_slow))`` clipped to ``[0, r - 1]``; ``ramp_i = clip((i
    - low) / (high - low), 0, 1)``; ``inter ramp + extra (1 - ramp)``."""
    i = jnp.arange(r // 2, dtype=F32)
    extra = theta ** (-2.0 * i / r)
    inter = extra / factor

    def dim(n):
        return r * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), r - 1)
    ramp = jnp.clip((i - low) / (0.001 if high == low else high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def rotary(x, inv_freq, factor):
    """``x [S, H, D]`` at positions 0..S-1: pairs ``(i, i + r/2)`` of the
    first ``r = 2 len(inv_freq)`` lanes rotate by ``pos * inv_freq[i]``,
    cosine and sine times ``factor``; the other lanes pass through."""
    S, half = x.shape[0], inv_freq.shape[0]
    ang = jnp.arange(S, dtype=F32)[:, None, None] * inv_freq[None, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def attention(a, lp, cfg):
    """``a [S, E]`` (normed) -> ``[S, E]``; ``lp`` float32; ``cfg`` holds
    this layer's ``kind`` and its rotary parameters."""
    S = a.shape[0]
    D, Hk = cfg["head_dim"], cfg["num_key_value_heads"]
    H = lp["wg"].shape[1]
    G = H // Hk
    q = (a @ lp["wq"]).reshape(S, H, D)
    kv = (a @ lp["wkv"]).reshape(S, 2 * Hk, D)
    z = jax.nn.sigmoid(a @ lp["wg"])                        # [S, H]
    r = int(D * cfg["partial_rotary_factor"])
    if cfg["kind"] == FULL:
        inv = yarn_inv_freq(r, cfg["rope_theta"], cfg["factor"],
                            cfg["original_max_position_embeddings"],
                            cfg["beta_fast"], cfg["beta_slow"])
        factor = cfg["attention_factor"]
    else:
        inv = cfg["rope_theta"] ** (-2.0 * jnp.arange(r // 2, dtype=F32) / r)
        factor = 1.0
    q, k, v = rotary(q, inv, factor), rotary(kv[:, :Hk], inv, factor), \
        kv[:, Hk:]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if cfg["kind"] == SLIDING:
        mask = mask & (j > i - cfg["sliding_window"])

    def kv_head(h):
        """The ``G`` query heads that read key-value head ``h``."""
        qh = jax.lax.dynamic_slice_in_dim(q, h * G, G, axis=1)   # [S, G, D]
        kh = jax.lax.dynamic_index_in_dim(k, h, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, h, 1, keepdims=False)
        s = jnp.einsum("qgd,kd->gqk", qh, kh) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        return jnp.einsum("gqk,kd->qgd", p, vh)

    o = jax.lax.map(kv_head, jnp.arange(Hk))                # [Hk, S, G, D]
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, D) * z[..., None]
    return o.reshape(S, H * D) @ lp["wo"]


def gated_ffn(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def expert_ffn(m, lp, cfg):
    """A sparse layer's FFN on ``m [S, E]``: the shared expert plus the
    held experts' part of the routed sum. ``lp["w_gu"]``/``lp["w_down"]``
    arrive in the type they are stored in and are upcast one expert at a
    time."""
    first = int(cfg["expert_offset"])
    s = jax.nn.sigmoid(m @ lp["router"])                    # [S, all]
    top, ids = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = cfg["moe_routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
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
    best logit left out, for one left out the weakest pick."""
    k = cfg["num_experts_per_tok"]
    first = int(cfg["expert_offset"])
    z = m @ lp["router"]                                    # [S, all]
    top = jax.lax.top_k(z, k + 1)[0]
    weakest_in, best_out = top[:, k - 1:k], top[:, k:]
    held = z[:, first:first + lp["w_gu"].shape[0]]
    edge = jnp.where(held >= weakest_in, held - best_out, weakest_in - held)
    return edge.min(-1) / z.std(-1)


def decoder_block(x, lp, cfg, margin=None):
    """One block on ``x [S, E]``; ``lp`` is ONE layer's weights (a sparse
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
        x = x + attention(rms_norm(x, lp["ln_in"], eps), lp, cfg)
        m = rms_norm(x, lp["ln_post"], eps)
        if "router" in lp:
            f = expert_ffn(m, lp, cfg)
            if margin is not None:
                margin = jnp.minimum(margin, routing_margin(m, lp, cfg))
        else:
            f = gated_ffn(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = x + f
        return x if margin is None else (x, margin)


# ---------------------------------------------------------------------------
# over the program's parameter tree
# ---------------------------------------------------------------------------

def embed(params: Dict[str, Any], ids):
    """``ids [S]`` -> what the walk carries: ``{"x": [S, E] float32,
    "margin": [S]}``, no sparse layer seen yet."""
    return {"x": jnp.asarray(params["embed"][ids], F32),
            "margin": jnp.full(ids.shape, jnp.inf, F32)}


def _stack(params):
    """``(P, S, T)``: whole periods, sliding layers a period, tail."""
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
    """The scalars block ``i`` needs, hashable: its kind with that kind's
    rotary parameters, and the widths every block shares."""
    kind = cfg["layer_types"][i]
    out = {k: cfg[k] for k in (
        "head_dim", "num_key_value_heads", "sliding_window", "rms_norm_eps",
        "num_experts_per_tok", "moe_routed_scaling_factor")}
    out.update(cfg["rope_parameters"][kind], kind=kind,
               expert_offset=cfg.get("expert_offset", 0))
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
    stacked weights and upcasts that layer alone (its routed experts one
    at a time), so the reference fits beside the served weights."""
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
    is judged."""
    tie = float(cfg.get("oracle", {}).get("tie_margin", 0.0))
    judged = state["margin"] >= tie
    return jnp.where(judged[:, None], logits(params, state["x"], cfg), 0.0)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any]):
    """``ids [S] -> logits [S, V]`` for one sequence, every position."""
    state = embed(params, ids)
    for i in range(n_blocks(params)):
        state = block(params, i, state, cfg)
    return logits(params, state["x"], cfg)
