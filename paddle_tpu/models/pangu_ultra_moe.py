"""The ``pangu_ultra_moe`` family (openPangu-Ultra-MoE): multi-head latent
attention, sandwich norms, routed experts beside a shared one, a stack
that is not uniform, served through the paged engine.

**The block** (``N_*`` an RMSNorm with its own weight)::

    y = MLA(N_in(x));        x = x + N_post_attn(y)
    f = FFN_l(N_pre_mlp(x)); x = x + N_post_mlp(f)

**MLA.** ``c_q = N_q(a W_qa)``; ``[q_nope | q_rope]_h = c_q W_qb``;
``[c_kv | k_rope] = a W_kva``; ``c_kv = N_kv(c_kv)``; rotary embedding on
interleaved pairs of ``q_rope`` and of ``k_rope`` (which all heads share);
``k_h = [c_kv W_kb,h^K | k_rope]``, ``v_h = c_kv W_kb,h^V``; causal
softmax of ``q_h . k_h / sqrt(d_nope + d_rope)``; ``concat_h(p v_h) W_o``.
The cache holds ``[c_kv | k_rope]`` alone: one vector a token a layer.
Serving computes the ABSORBED form, the same numbers: ``q~_h = q_nope,h
(W_kb,h^K)^T`` carries the query into the compressed space, the score is
``(q~_h . c_kv + q_rope,h . k_rope) / sqrt(..)``, ``ctx_h = sum_j p_j
c_kv,j`` and the head's output is ``ctx_h W_kb,h^V``; per-head keys and
values of the cached context are never made. :func:`forward` computes
either form (``absorbed=``) over whole sequences, for the tests.

**FFN.** A leading dense layer is a gated FFN. An expert layer scores all
``n_routed_experts`` in float32 (``sigmoid``), takes the top
``num_experts_per_tok``, weighs them ``routed_scaling_factor * s / (sum s
+ 1e-20)``, and adds the shared expert. **It is told which experts it
holds** (``n_local_experts`` from ``expert_offset`` on: one chip's share
of an expert-parallel deployment): it routes over all of them and
computes the part of the sum its own experts give. Pairs are sorted by
expert; pairs on experts held elsewhere and pairs of lanes that carry no
token are not computed; no pair on a held expert is ever dropped
(:func:`paddle_tpu.kernels.grouped_matmul`).

**The stack** is a list of runs of like layers, a ``lax.scan`` a run:
``params["runs"]`` holds one stacked tree a run, dense or expert by
whether it has a ``router``.

**Paged serving.** The engine reaches these entry points through
``PanguUltraMoEConfig.paged_family`` (``models.paged_family``). Every
path is ONE function over query LANES (:func:`_paged_lanes`): a lane is
one token with its position, the table row it reads and whether it is
real; a decode step is a lane a slot, a prefill a lane a prompt token, a
mixed step its real lanes packed to the front and run in waves
(:func:`paged_mixed_step`); a lane that is not real computes no attention
and no routed expert. The pool is ``{"kv": [L, N, bs, D]}``, ``D`` the latent
width (``kv_lora_rank + qk_rope_head_dim``) rounded up to whole 128-lane
tiles (:func:`paddle_tpu.kernels.paged_attention_latent` says why); the
kernel reads a layer of it by index, so the pool is carried whole through
the scans and never sliced. Each entry point returns, third, the counters
of its dispatch (``PAGED_COUNTERS``), which the engine sums into
``stats()["spans"]["counters"]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .llama import _rms_norm

__all__ = ["PanguUltraMoEConfig", "init_params", "forward", "num_params",
           "init_paged_pool", "paged_pool_block_bytes", "paged_prefill",
           "paged_decode_step", "paged_mixed_step",
           "PAGED_COUNTERS", "validate_serving", "describe", "health"]

# what one dispatch counts on the device, in this order (int32, summed over
# layers and iterations): (token, pick) pairs of real lanes; those on
# experts held here; (layer, expert) calls that had at least one row; the
# largest row count of one held expert, summed a layer call; cache tokens
# the latent attention read; lanes run through the model's dense parts
# (real or not: a mixed step's waves, a prefill's bucket, a decode step's
# slots)
PAGED_COUNTERS = ("moe_pairs_total", "moe_pairs_local", "moe_expert_calls",
                  "moe_rows_max", "latent_tokens_read", "lanes_computed")
_LANES = 128
# lanes of one wave of a packed mixed step, in multiples of the slots
_WAVE_ROWS = 4


@dataclasses.dataclass(frozen=True)
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432         # the leading dense layers' FFN
    moe_intermediate_size: int = 2048      # every expert's, the shared too
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3         # leading dense layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256            # the router's width
    n_local_experts: int = 256             # experts held HERE ...
    expert_offset: int = 0                 # ... from this id on
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    max_position_embeddings: int = 131072
    dtype: Any = jnp.float32               # activation / compute dtype
    param_dtype: Any = jnp.float32         # storage dtype

    # where the serving engine finds this family's paged entry points
    paged_family = "paddle_tpu.models.pangu_ultra_moe"
    # the router's score and the experts' activation (:func:`route`,
    # :func:`_routed_experts`); another family may set them otherwise
    router_score = "sigmoid"
    expert_activation = "silu"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the stack")
        if self.expert_offset < 0 or (self.expert_offset +
                                      self.n_local_experts
                                      > self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset}+"
                f"{self.n_local_experts} are not among the "
                f"{self.n_routed_experts} the router scores")

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """The stack as runs of like layers: ``(kind, layers)``."""
        d = self.first_k_dense_replace
        runs = (("dense", d), ("moe", self.num_hidden_layers - d))
        return tuple(r for r in runs if r[1])

    @property
    def latent_dim(self) -> int:
        """What the cache holds a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_dim(self) -> int:
        """The pool's last dimension: whole 128-lane tiles."""
        return -(-self.latent_dim // _LANES) * _LANES

    @property
    def attn_scale(self) -> float:
        return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def describe(cfg: PanguUltraMoEConfig) -> Dict[str, Any]:
    """The widths and counts a reader of ``stats()`` needs to turn this
    family's counters into bytes and operations, under the published
    names (``stats()["model"]``)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if f.name not in ("dtype", "param_dtype")}
    out.update(family="pangu_ultra_moe", dtype=jnp.dtype(cfg.dtype).name,
               pool_dim=cfg.pool_dim)
    return out


def health(counters: Dict[str, int], cfg: PanguUltraMoEConfig) -> Dict:
    """``health_snapshot()["family"]``: the share of (token, pick) pairs
    that fell on experts held here, in percent, and the fullest held
    expert's rows over the mean, averaged over layer calls; each None
    before the first dispatch."""
    total, local = (counters.get("moe_pairs_total"),
                    counters.get("moe_pairs_local"))
    return {
        "local_pair_pct": round(100.0 * local / total, 2) if total else None,
        "load_max_over_mean": (
            round(counters["moe_rows_max"] * cfg.n_local_experts / local, 3)
            if local else None)}


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: PanguUltraMoEConfig, kind: str) -> Dict[str, tuple]:
    E, H = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
    shapes = {"ln_in": (E,), "ln_post_attn": (E,), "ln_pre_mlp": (E,),
              "ln_post_mlp": (E,), "ln_q": (Rq,), "ln_kv": (R,),
              "wq_a": (E, Rq), "wq_b": (Rq, H * (dn + dr)),
              "wkv_a": (E, R + dr),
              # per head [key part | value part] out of the compressed
              # vector, kept [R, H, dn + dv] so both forms einsum over it
              "wkv_b": (R, H, dn + dv),
              "wo": (H * dv, E)}
    if kind == "dense":
        I = cfg.intermediate_size
        shapes.update(w_gate=(E, I), w_up=(E, I), w_down=(I, E))
    else:
        I, Is = cfg.moe_intermediate_size, (cfg.moe_intermediate_size *
                                            cfg.n_shared_experts)
        shapes.update(router=(E, cfg.n_routed_experts),
                      ws_gate=(E, Is), ws_up=(E, Is), ws_down=(Is, E),
                      # the held experts: [gate | up] side by side, so one
                      # grouped matmul makes both
                      w_gu=(cfg.n_local_experts, E, 2 * I),
                      w_down=(cfg.n_local_experts, I, E))
    return shapes


def fan_in(name: str, shape: tuple) -> int:
    """Rows a weight contracts over (``wkv_b`` keeps its heads apart)."""
    return shape[-3] if name == "wkv_b" else shape[-2]


def init_params(cfg: PanguUltraMoEConfig, key: jax.Array) -> Dict:
    """``{"embed", "ln_f", "lm_head", "runs": [stacked tree a run]}``;
    norms at one, matrices normal with variance ``1 / fan_in``."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 64))

    def dense(shape, rows):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w * float(rows) ** -0.5).astype(pd)

    runs = []
    for kind, n in cfg.runs:
        run = {}
        for name, shape in _layer_shapes(cfg, kind).items():
            full = (n,) + shape
            run[name] = (jnp.ones(full, pd) if name.startswith("ln")
                         else dense(full, fan_in(name, shape)))
        runs.append(run)
    E, V = cfg.hidden_size, cfg.vocab_size
    return {"embed": dense((V, E), 1.0), "ln_f": jnp.ones((E,), pd),
            "lm_head": dense((E, V), E), "runs": runs}


def num_params(cfg: PanguUltraMoEConfig) -> int:
    import math
    n = 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    for kind, layers in cfg.runs:
        n += layers * sum(math.prod(s)
                          for s in _layer_shapes(cfg, kind).values())
    return n


# ---------------------------------------------------------------------------
# the layer's parts
# ---------------------------------------------------------------------------

def _norm(x, w, cfg):
    return _rms_norm(x, w, cfg.rms_norm_eps, False)


def _rope_tables(cfg, pos):
    """``cos, sin [.., Dr / 2]`` (float32) at integer positions ``pos``."""
    half = cfg.qk_rope_head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """Rotate interleaved pairs ``(2i, 2i + 1)`` of the last axis."""
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    y = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return y.reshape(x.shape).astype(x.dtype)


def _mla_project(lp, a, cos, sin, cfg):
    """``a [T, E]`` (normed) -> ``q_nope [T, H, dn]``, ``q_rope [T, H,
    dr]``, the cache entry's parts ``c_kv [T, R]`` and ``k_rope [T, dr]``.
    ``cos``/``sin [T, dr / 2]``."""
    dt = cfg.dtype
    H, dn, R = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    T = a.shape[0]
    cq = _norm(a @ lp["wq_a"].astype(dt), lp["ln_q"], cfg)
    q = (cq @ lp["wq_b"].astype(dt)).reshape(T, H, -1)
    kv = a @ lp["wkv_a"].astype(dt)
    c_kv = _norm(kv[:, :R], lp["ln_kv"], cfg)
    k_rope = _rope(kv[:, R:], cos, sin)
    q_rope = _rope(q[..., dn:], cos[:, None], sin[:, None])
    return q[..., :dn], q_rope, c_kv, k_rope


def _dense_ffn(m, wg, wu, wd, dt):
    g = jax.nn.silu(m @ wg.astype(dt)) * (m @ wu.astype(dt))
    return g @ wd.astype(dt)


def route(lp, m, cfg):
    """Float32 router on ``m [T, E]``: ``(ids [T, k], weights [T, k])``
    over ALL ``n_routed_experts``. Scores ``sigmoid`` or ``softmax`` of
    the logits (``cfg.router_score``); the top-k by score, weighed
    ``routed_scaling_factor * s / sum s`` (for the softmax that is the
    softmax over the picks' logits)."""
    z = m.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    s = (jax.nn.softmax(z, -1) if cfg.router_score == "softmax"
         else jax.nn.sigmoid(z))
    top, ids = lax.top_k(s, cfg.num_experts_per_tok)
    w = cfg.routed_scaling_factor * top / (top.sum(-1, keepdims=True) + 1e-20)
    return ids, w


def _routed_experts(lp, m, real, cfg, use_kernel, layer=None,
                    router_input=None):
    """The held experts' part of the routed sum on ``m [T, E]`` for the
    lanes ``real [T]``, and this call's counters ``[4]`` (the first four
    of ``PAGED_COUNTERS``). The router reads ``router_input [T, E]`` where
    given, else ``m``. With ``layer`` (a traced index), ``lp["w_gu"]`` and
    ``lp["w_down"]`` are a run's experts still stacked over its layers
    and the grouped matmul reads layer ``layer`` of them in place."""
    from ..kernels.grouped_matmul import grouped_matmul
    T, k, Eh = m.shape[0], cfg.num_experts_per_tok, cfg.n_local_experts
    ids, w = route(lp, m if router_input is None else router_input, cfg)
    here = ((ids >= cfg.expert_offset) & (ids < cfg.expert_offset + Eh)
            & real[:, None])
    # sort the (token, pick) pairs by held expert; a pair that is not this
    # chip's, or a pad lane's, takes the key past the last group and sorts
    # to the tail, where the grouped matmul computes nothing
    key = jnp.where(here, ids - cfg.expert_offset, Eh).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    lane = (jnp.arange(T * k, dtype=jnp.int32) // k)[order]
    rows = m[lane]
    h = grouped_matmul(rows, lp["w_gu"], sizes, layer=layer, gated=True,
                       activation=cfg.expert_activation,
                       use_kernel=use_kernel)
    y = grouped_matmul(h, lp["w_down"], sizes, layer=layer,
                       use_kernel=use_kernel)
    wy = y.astype(jnp.float32) * jnp.where(
        key[order] < Eh, w.reshape(-1)[order], 0.0)[:, None]
    out = jnp.zeros((T, m.shape[1]), jnp.float32).at[lane].add(wy)
    counts = jnp.stack([real.sum().astype(jnp.int32) * k, sizes.sum(),
                        (sizes > 0).sum().astype(jnp.int32), sizes.max()])
    return out.astype(m.dtype), counts


def _ffn(lp, m, real, cfg, use_kernel, layer=None, router_input=None):
    """``(f [T, E], counters [4])`` of one layer's FFN, dense or expert by
    what the layer holds, the shared expert added where it holds one
    (``layer``, ``router_input``: see :func:`_routed_experts`)."""
    dt = cfg.dtype
    if "router" not in lp:
        return (_dense_ffn(m, lp["w_gate"], lp["w_up"], lp["w_down"], dt),
                jnp.zeros((4,), jnp.int32))
    routed, counts = _routed_experts(lp, m, real, cfg, use_kernel, layer,
                                     router_input)
    if "ws_gate" not in lp:
        return routed, counts
    shared = _dense_ffn(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dt)
    return shared + routed, counts


def _head(params, x, cfg):
    x = _norm(x, params["ln_f"], cfg)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# whole sequences, no cache: both forms of the attention, for the tests
# ---------------------------------------------------------------------------

def _attend_sequences(lp, qn, qr, c, kr, cfg, absorbed):
    """Causal attention of ``B`` whole sequences: ``qn [B, S, H, dn]``,
    ``qr [B, S, H, dr]``, ``c [B, S, R]``, ``kr [B, S, dr]`` -> ``[B, S, H *
    dv]``, in the expanded (per-head keys and values) or the absorbed
    form."""
    f32 = jnp.float32
    dn = cfg.qk_nope_head_dim
    wkb = lp["wkv_b"].astype(f32)
    qn, qr, c, kr = (t.astype(f32) for t in (qn, qr, c, kr))
    S = c.shape[1]
    if absorbed:        # queries into the compressed space, values = c
        keys = c
        q = jnp.einsum("bqhd,rhd->bqhr", qn, wkb[..., :dn])
        s = jnp.einsum("bqhr,bkr->bhqk", q, keys)
    else:               # per-head keys and values out of it
        keys = jnp.einsum("bkr,rhd->bkhd", c, wkb[..., :dn])
        s = jnp.einsum("bqhd,bkhd->bhqk", qn, keys)
    s = s + jnp.einsum("bqhd,bkd->bhqk", qr, kr)
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    p = jax.nn.softmax(jnp.where(mask, s * cfg.attn_scale, -1e30), -1)
    if absorbed:
        ctx = jnp.einsum("bhqk,bkr->bqhr", p, c)
        o = jnp.einsum("bqhr,rhv->bqhv", ctx, wkb[..., dn:])
    else:
        v = jnp.einsum("bkr,rhv->bkhv", c, wkb[..., dn:])
        o = jnp.einsum("bhqk,bkhv->bqhv", p, v)
    return o.reshape(o.shape[0], S, -1).astype(cfg.dtype)


def forward(params: Dict, ids, cfg: PanguUltraMoEConfig, absorbed=False,
            use_kernel: bool = False):
    """``ids [B, S] -> logits [B, S, V]`` (float32), no cache."""
    B, S = ids.shape
    dt = cfg.dtype
    x = jnp.take(params["embed"], ids.reshape(-1), axis=0).astype(dt)
    cos, sin = _rope_tables(cfg, jnp.tile(jnp.arange(S), B))
    real = jnp.ones((B * S,), bool)

    def body(x, lp):
        a = _norm(x, lp["ln_in"], cfg)
        qn, qr, c, kr = _mla_project(lp, a, cos, sin, cfg)
        o = _attend_sequences(
            lp, *(t.reshape((B, S) + t.shape[1:]) for t in (qn, qr, c, kr)),
            cfg, absorbed).reshape(B * S, -1)
        x = x + _norm(o @ lp["wo"].astype(dt), lp["ln_post_attn"], cfg)
        f, _ = _ffn(lp, _norm(x, lp["ln_pre_mlp"], cfg), real, cfg,
                    use_kernel)
        return x + _norm(f, lp["ln_post_mlp"], cfg), None

    for run in params["runs"]:
        x, _ = lax.scan(body, x, run)
    return _head(params, x, cfg).reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the paged pool
# ---------------------------------------------------------------------------

def validate_serving(cfg: PanguUltraMoEConfig, serving_config) -> None:
    """What the paged engine offers and this family does not serve is an
    error at construction, never a silent fall-back."""
    sc = serving_config
    off = [name for name, on in (
        ("lora_slots", sc.lora_slots), ("kv_quant", sc.kv_quant),
        ("quantize", sc.quantize), ("tp > 1", sc.tp > 1),
        ("spec_decode", sc.spec_decode)) if on]
    if off:
        raise ValueError(
            f"the pangu_ultra_moe family does not serve with {off}: its "
            f"paged programs take bf16/fp32 weights and one latent pool on "
            f"one device, and have no verify step")


def init_paged_pool(cfg: PanguUltraMoEConfig, num_blocks: int,
                    block_size: int, dtype=None, kv_quant=None,
                    mesh=None) -> Dict:
    """``{"kv": [L, num_blocks, block_size, D]}``: one latent vector a
    token a layer (``[c_kv | k_rope]``, then padding to whole tiles).
    Block 0 is the null block, as in every pool of the engine."""
    if kv_quant is not None or mesh is not None:
        raise ValueError("the latent pool is neither quantized nor sharded")
    dt = dtype if dtype is not None else cfg.dtype
    return {"kv": jnp.zeros((cfg.num_hidden_layers, num_blocks, block_size,
                             cfg.pool_dim), dt)}


def paged_pool_block_bytes(cfg: PanguUltraMoEConfig, block_size: int,
                           dtype=None, kv_quant=None, tp: int = 1) -> int:
    """Bytes one physical block costs across all layers."""
    if kv_quant is not None or tp != 1:
        raise ValueError("the latent pool is neither quantized nor sharded")
    dt = dtype if dtype is not None else cfg.dtype
    return (cfg.num_hidden_layers * int(block_size) * cfg.pool_dim *
            jnp.dtype(dt).itemsize)


# ---------------------------------------------------------------------------
# paged serving: one forward over query lanes
# ---------------------------------------------------------------------------

def _attend_gathered(q_lat, q_rope, kv, layer, block_tables, slot, lens, cfg):
    """The latent attention in plain XLA (the oracle of the kernel, and
    the path off the TPU): gather each lane's table row out of layer
    ``layer`` and mask by length."""
    f32 = jnp.float32
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    S, W = block_tables.shape
    page = lax.dynamic_index_in_dim(kv, layer, 0, keepdims=False)
    ctxt = page[block_tables].reshape(S, W * page.shape[1], -1)[slot]
    live = jnp.arange(ctxt.shape[1])[None, :] < lens[:, None]      # [T, C]
    # a value no lane may attend is ZEROED (poison containment, as in
    # llama._masked_sdpa); a score there is replaced
    c = jnp.where(live[..., None], ctxt[..., :R], 0).astype(f32)
    s = (jnp.einsum("thr,tcr->thc", q_lat.astype(f32), c) +
         jnp.einsum("thd,tcd->thc", q_rope.astype(f32),
                    ctxt[..., R:R + dr].astype(f32))) * cfg.attn_scale
    p = jax.nn.softmax(jnp.where(live[:, None], s, -1e30), axis=-1)
    return jnp.einsum("thc,tcr->thr", p, c).astype(q_lat.dtype)


def _paged_lanes(params, cfg, tokens, pos, slot, real, block_tables, pool,
                 use_kernel):
    """The whole model over ``T`` query lanes against the pool: lane ``t``
    carries token ``tokens[t]`` at position ``pos[t]`` of table row
    ``slot[t]``; a lane that is not ``real`` writes to the null block,
    attends nothing and feeds no routed expert. Returns ``(x [T, E], pool,
    counters)``."""
    dt = cfg.dtype
    kv = pool["kv"]
    bs, D = kv.shape[2], kv.shape[3]
    W = block_tables.shape[1]
    dn, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    T = tokens.shape[0]
    cos, sin = _rope_tables(cfg, pos)
    page = block_tables[slot, jnp.minimum(pos // bs, W - 1)]
    phys = jnp.where(real, page, 0)
    off = pos % bs
    lens = jnp.where(real, pos + 1, 0).astype(jnp.int32)
    pad = jnp.zeros((T, D - cfg.latent_dim), kv.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)

    def body(carry, xs, experts):
        x, kv, counts = carry
        lp, layer, in_run = xs
        lp = {**lp, **experts}
        a = _norm(x, lp["ln_in"], cfg)
        qn, qr, c, kr = _mla_project(lp, a, cos, sin, cfg)
        kv = kv.at[layer, phys, off].set(jnp.concatenate(
            [c.astype(kv.dtype), kr.astype(kv.dtype), pad], axis=-1))
        wkb = lp["wkv_b"].astype(dt)
        q_lat = jnp.einsum("thd,rhd->thr", qn, wkb[..., :dn])
        if use_kernel:
            from ..kernels.paged_attention import paged_attention_latent
            ctx = paged_attention_latent(q_lat, qr, kv, layer, block_tables,
                                         slot, lens, cfg.attn_scale,
                                         out_dtype=dt)
        else:
            ctx = _attend_gathered(q_lat, qr, kv, layer, block_tables, slot,
                                   lens, cfg)
        o = jnp.einsum("thr,rhv->thv", ctx, wkb[..., dn:]).reshape(T, -1)
        x = x + _norm(o @ lp["wo"].astype(dt), lp["ln_post_attn"], cfg)
        f, moe = _ffn(lp, _norm(x, lp["ln_pre_mlp"], cfg), real, cfg,
                      use_kernel, in_run if experts else None)
        x = x + _norm(f, lp["ln_post_mlp"], cfg)
        return (x, kv, counts.at[:5].add(
            jnp.concatenate([moe, lens.sum()[None]]))), None

    counts = jnp.zeros((len(PAGED_COUNTERS),), jnp.int32).at[5].set(T)
    first = 0
    for run in params["runs"]:
        n = run["ln_in"].shape[0]
        # a run's routed experts stay stacked and OUT of the scan's
        # operands: sliced a layer, each would be copied whole (1.5 GB a
        # layer here) before the kernel that reads it
        experts = {k: run[k] for k in ("w_gu", "w_down") if "router" in run}
        (x, kv, counts), _ = lax.scan(
            functools.partial(body, experts=experts), (x, kv, counts),
            ({k: v for k, v in run.items() if k not in experts},
             jnp.arange(first, first + n, dtype=jnp.int32),
             jnp.arange(n, dtype=jnp.int32)))
        first += n
    return x, {"kv": kv}, counts


def _no_lora(lora):
    if lora is not None:
        raise ValueError("the pangu_ultra_moe family serves no adapters")


def paged_prefill(params: Dict, cfg: PanguUltraMoEConfig, ids, prompt_lens,
                  block_tables, pool: Dict, active, lora=None,
                  use_kernel: bool = False):
    """``generation.paged_prefill``'s contract: ``ids [B, Sb]``
    right-padded prompts with no cached prefix -> (next-token logits ``[B,
    V]`` at ``prompt_lens - 1``, pool, counters). A lane a token, through
    the cache like every other path."""
    _no_lora(lora)
    B, Sb = ids.shape
    j = jnp.tile(jnp.arange(Sb, dtype=jnp.int32), B)
    row = jnp.repeat(jnp.arange(B, dtype=jnp.int32), Sb)
    real = (j < prompt_lens[row]) & active[row]
    x, pool, counts = _paged_lanes(params, cfg, ids.reshape(-1), j, row, real,
                                   block_tables, pool, use_kernel)
    last = jnp.take_along_axis(
        x.reshape(B, Sb, -1), jnp.maximum(prompt_lens - 1, 0)[:, None, None],
        axis=1)[:, 0]
    return _head(params, last, cfg), pool, counts


def paged_decode_step(params: Dict, cfg: PanguUltraMoEConfig, tokens,
                      seq_lens, block_tables, pool: Dict, active,
                      use_kernel: bool = False, lora=None):
    """``generation.paged_decode_step``'s contract: one token a slot at
    position ``seq_lens`` -> (logits ``[M, V]``, pool, counters)."""
    _no_lora(lora)
    M = tokens.shape[0]
    x, pool, counts = _paged_lanes(
        params, cfg, tokens, seq_lens, jnp.arange(M, dtype=jnp.int32),
        active, block_tables, pool, use_kernel)
    return _head(params, x, cfg), pool, counts


def paged_mixed_step(params: Dict, cfg: PanguUltraMoEConfig, tokens, starts,
                     q_lens, block_tables, pool: Dict, active,
                     use_kernel: bool = False, lora=None):
    """``generation.paged_mixed_step``'s contract: row ``m`` carries
    ``q_lens[m]`` real tokens from position ``starts[m]`` on (one for a
    decoding slot, a chunk for a prompt in prefill) -> (logits ``[M, V]``
    after each row's last real token, pool, counters).

    **The step is packed.** Of the ``M x Q`` lanes the engine hands over
    only the real ones are computed: they are gathered to the front in
    row-major order and run through the model in WAVES of ``_WAVE_ROWS x
    M`` lanes (one wave in steady state: a step's decoding slots and a
    chunk or two), each wave the whole forward against the pool. A later
    wave's lanes sit at later positions of the same rows or in later
    rows, and everything a lane attends is in the pool by the time its
    wave runs: the waves before it wrote theirs, its own wave scatters
    every lane's entry before any lane attends. Pad lanes of the last
    wave write to the null block, attend nothing and feed no expert."""
    _no_lora(lora)
    M, Q = tokens.shape
    T = M * Q
    Tw = min(T, _WAVE_ROWS * M)
    q = jnp.tile(jnp.arange(Q, dtype=jnp.int32), M)
    row = jnp.repeat(jnp.arange(M, dtype=jnp.int32), Q)
    real = (q < q_lens[row]) & active[row]
    toks, pos = tokens.reshape(-1), starts[row] + q
    n_real = real.sum().astype(jnp.int32)
    # real lanes first, in the order they had; whole waves
    order = jnp.argsort(~real, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -T % Tw))
    # where each row's last real lane lands: its rank among the real lanes
    last_flat = jnp.arange(M, dtype=jnp.int32) * Q + jnp.maximum(q_lens - 1, 0)
    rank = (jnp.cumsum(real.astype(jnp.int32)) - 1)[last_flat]
    has = active & (q_lens > 0)

    def wave(carry):
        w, pool, x_last, counts = carry
        idx = lax.dynamic_slice_in_dim(order, w * Tw, Tw)
        live = w * Tw + jnp.arange(Tw, dtype=jnp.int32) < n_real
        x, pool, c = _paged_lanes(params, cfg, toks[idx], pos[idx], row[idx],
                                  live, block_tables, pool, use_kernel)
        mine = has & (rank // Tw == w)
        x_last = jnp.where(mine[:, None], x[rank % Tw], x_last)
        return w + 1, pool, x_last, counts + c

    _, pool, x_last, counts = lax.while_loop(
        lambda carry: carry[0] * Tw < n_real, wave,
        (jnp.int32(0), pool,
         jnp.zeros((M, cfg.hidden_size), cfg.dtype),
         jnp.zeros((len(PAGED_COUNTERS),), jnp.int32)))
    return _head(params, x_last, cfg), pool, counts
