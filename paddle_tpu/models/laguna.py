"""The window/full family (Laguna-S, SmallThinker): window and full
attention layers mixed, a head count a kind of layer, routed experts,
served through the paged engine with ONE block pool for both kinds of
layer. What differs between the architectures is static configuration,
whose defaults are Laguna's (:class:`LagunaConfig`): the rotary embedding
of each kind of layer (or none), a per-head gate or none, a dense layer 0
or a sparse one, the router's input and score, the experts' activation, a
shared expert or none.

**The block** (pre-norm; ``N_*`` an RMSNorm with its own weight, ``l`` the
layer, ``H_l`` its query heads)::

    a = N_in(x);    x = x + Attn_l(a)
    m = N_post(x);  x = x + FFN_l(m)

**Attention.** ``q = a W_q`` as ``H_l`` heads of ``head_dim``; ``[k | v] =
a W_kv`` as ``num_key_value_heads`` heads each; no bias, no normalisation
of queries or keys. Rotary embedding by the KIND of layer, on rotate-half
pairs ``(i, i + r / 2)`` of the first ``r`` lanes, the rest passing
through: a sliding layer plain (``r`` = all lanes, ``theta`` 1e4), a full
layer YaRN on half the lanes (:func:`rope_inv_freq`), or none where
``full_rope_type`` is ``"none"`` (NoPE). Query head ``h`` reads key-value
head ``h // (H_l / Hk)``; position ``i`` attends ``j <= i`` and, in a
sliding layer, only ``j > i - sliding_window``. The gate (``head_gate``):
``z = sigmoid(a W_g)`` (``W_g [E, H_l]``), output ``concat_h(z_h o_h)
W_o``; without it ``concat_h(o_h) W_o``.

**FFN.** Layer 0 is a gated dense FFN (``first_layer_dense``) or a sparse
layer like the others. A sparse layer scores all ``num_experts`` in
float32 (``router_score``: ``sigmoid``, or ``softmax``), takes the top
``num_experts_per_tok``, weighs them ``moe_routed_scaling_factor * s /
sum s``, and adds the shared expert ungated where it has one
(``shared_expert_intermediate_size`` 0: none); an expert is gated SiLU or
ReLU (``expert_activation``). The router reads ``m`` or, with
``router_before_attention``, the layer's input ``x`` itself, on which
attention has no bearing, so the routing may run beside it. It holds
``n_local_experts`` experts from ``expert_offset`` on and computes their
part of the sum (the routing, the sort by expert and the grouped matmul
are :mod:`.pangu_ultra_moe`'s, under the same counters).

**The stack.** Layer 0 alone, then a ``lax.scan`` over PERIODS (a period's
sliding layers then its full layer as the scan's ``xs``), then the
sliding layers a cut depth leaves after the last whole period: two scans
at any depth. The routed experts stay stacked and out of the ``xs``; the
grouped matmul reads a layer of them in place.

**The cache: a specification a KIND of layer** (:func:`paged_cache_groups`).
Layers that see the same positions share a block table: the full layers
are one group, unbounded; the sliding layers are split, in order, into
groups of as many layers as there are full ones, each bounded by a RING of
``ring_blocks`` table entries (position ``j`` at entry ``(j // bs) %
R``), which holds ``sliding_window + window_chunk`` tokens whatever the
sequence's length. Equal groups make a physical block cost the same bytes
whoever takes it, so one allocator serves all: the pool is ``{"k", "v":
[layers a group, N, bs, Hk, D]}``, axis 0 the layer's index INSIDE its
group, and a table row holds the groups' entries side by side, ``[full |
ring 1 | ring 2 ...]``. A full layer calls ``kernels.paged_attention`` as
the dense family does; a sliding layer its window-bounded form on its
group's ring, which copies the pages from the first query's lowest
position on and no other.

**Paged serving.** One forward over query LANES (:func:`_paged_rows`), as
``generation._paged_multiquery_forward`` has it: everything a token is
computed on ``[lanes, E]``; attention alone sees rows ``[M, Q, H, D]``
with each row's ``(start, draft_len)``. A decode step and a prefill run
their lanes in place, a mixed step packs its real lanes into waves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .generation import _kv_gather, _kv_store
from .llama import _masked_sdpa, _rms_norm
from .pangu_ultra_moe import _ffn

__all__ = ["LagunaConfig", "init_params", "forward", "num_params",
           "init_paged_pool", "paged_pool_block_bytes", "paged_cache_groups",
           "mixed_lane_budget", "paged_prefill", "paged_decode_step",
           "paged_mixed_step",
           "PAGED_COUNTERS", "validate_serving", "describe", "health"]

# what one dispatch counts on the device, in this order (int32, summed
# over layers and iterations): the routed experts' four as the
# pangu_ultra_moe family counts them; live cache tokens a query lane
# read, summed over lanes and layers of that kind; cache tokens a ROW's
# attention call copied (whole pages, which its query lanes share), summed
# over rows, waves and layers of that kind; lanes run through the model's
# per-token parts, real or not
PAGED_COUNTERS = ("moe_pairs_total", "moe_pairs_local", "moe_expert_calls",
                  "moe_rows_max", "full_tokens_read", "window_tokens_read",
                  "full_tokens_copied", "window_tokens_copied",
                  "lanes_computed")
FULL, SLIDING = "full_attention", "sliding_attention"
# where a layer of each kind adds its (tokens read, tokens copied)
_READ_AT = {FULL: [PAGED_COUNTERS.index("full_tokens_read"),
                   PAGED_COUNTERS.index("full_tokens_copied")],
            SLIDING: [PAGED_COUNTERS.index("window_tokens_read"),
                      PAGED_COUNTERS.index("window_tokens_copied")]}
# lanes of one wave of a packed mixed step, in multiples of the slots
_WAVE_ROWS = 32


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288         # layer 0's dense FFN
    moe_intermediate_size: int = 1024      # a routed expert's
    shared_expert_intermediate_size: int = 1024
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 12
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    sliding_window: int = 512
    num_experts: int = 256                 # the router's width
    n_local_experts: int = 256             # experts held HERE ...
    expert_offset: int = 0                 # ... from this id on
    num_experts_per_tok: int = 10
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # rotary embedding of a full layer (YaRN) and of a sliding one (plain)
    full_rope_theta: float = 500000.0
    full_rotary_factor: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    sliding_rope_theta: float = 10000.0
    sliding_rotary_factor: float = 1.0
    full_rope_type: str = "yarn"           # or "none": no rotary (NoPE)
    # the architecture's other differences, Laguna's by default
    first_layer_dense: bool = True         # layer 0's FFN dense, or sparse
    head_gate: bool = True                 # sigmoid gate a query head
    router_before_attention: bool = False  # the router reads x, not m
    router_score: str = "sigmoid"          # or "softmax"
    expert_activation: str = "silu"        # or "relu" (ReGLU)
    # the architecture's name, which ``describe`` gives
    family: str = "laguna"
    # the most query tokens of one sequence a dispatch carries (the
    # engine's ``prefill_chunk``): with the window, what a ring holds
    window_chunk: int = 128
    dtype: Any = jnp.float32               # activation / compute dtype
    param_dtype: Any = jnp.float32         # storage dtype

    # where the serving engine finds this family's paged entry points
    paged_family = "paddle_tpu.models.laguna"

    def __post_init__(self):
        L, kinds = self.num_hidden_layers, tuple(self.layer_types)
        heads = tuple(self.num_attention_heads_per_layer)
        if len(kinds) != L or len(heads) != L:
            raise ValueError(f"layer_types and num_attention_heads_per_layer "
                             f"name {len(kinds)} and {len(heads)} layers, "
                             f"num_hidden_layers {L}")
        if kinds[0] != FULL or set(kinds) - {FULL, SLIDING}:
            raise ValueError("layer 0 is a full_attention layer and every "
                             "layer full_attention or sliding_attention")
        S = self.slides_per_period
        want = ((SLIDING,) * S + (FULL,)) * self.n_periods + \
            (SLIDING,) * self.n_tail
        if kinds[1:] != want:
            raise ValueError("after layer 0 the stack is whole periods of "
                             "sliding layers then a full one, then sliding "
                             f"layers only; got {kinds}")
        for kind in (FULL, SLIDING):
            if len({h for h, k in zip(heads, kinds) if k == kind}) > 1:
                raise ValueError(f"{kind} layers differ in their head count")
        if any(h % self.num_key_value_heads for h in heads):
            raise ValueError("a layer's query heads divide by "
                             "num_key_value_heads")
        if self.n_sliding % self.n_full:
            raise ValueError(
                f"{self.n_sliding} sliding layers do not split into cache "
                f"groups of {self.n_full} (the full layers' count): cut the "
                f"depth at whole periods")
        for name, allowed in (("full_rope_type", ("yarn", "none")),
                              ("router_score", ("sigmoid", "softmax")),
                              ("expert_activation", ("silu", "relu"))):
            if getattr(self, name) not in allowed:
                raise ValueError(f"{name} is one of {allowed}, got "
                                 f"{getattr(self, name)!r}")
        if self.expert_offset < 0 or (self.expert_offset +
                                      self.n_local_experts
                                      > self.num_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset}+"
                f"{self.n_local_experts} are not among the "
                f"{self.num_experts} the router scores")

    # ---- the stack's shape
    @property
    def slides_per_period(self) -> int:
        """Sliding layers before each full one after layer 0."""
        kinds = tuple(self.layer_types)[1:]
        return kinds.index(FULL) if FULL in kinds else 0

    @property
    def n_periods(self) -> int:
        return tuple(self.layer_types)[1:].count(FULL)

    @property
    def n_tail(self) -> int:
        """Sliding layers after the last whole period."""
        return (self.num_hidden_layers - 1 -
                self.n_periods * (self.slides_per_period + 1))

    @property
    def n_full(self) -> int:
        return 1 + self.n_periods

    @property
    def n_sliding(self) -> int:
        return self.num_hidden_layers - self.n_full

    @property
    def heads_full(self) -> int:
        return self.num_attention_heads_per_layer[0]

    @property
    def heads_sliding(self) -> int:
        return next((h for h, k in zip(self.num_attention_heads_per_layer,
                                       self.layer_types) if k == SLIDING), 0)

    @property
    def n_sparse(self) -> int:
        """Layers with routed experts."""
        return self.num_hidden_layers - int(self.first_layer_dense)

    @property
    def window_groups(self) -> int:
        return self.n_sliding // self.n_full

    def ring_blocks(self, block_size: int) -> int:
        """Table entries of a window group's ring: the blocks that
        ``sliding_window + window_chunk`` tokens touch, plus one."""
        return -(-(self.sliding_window + self.window_chunk)
                 // int(block_size)) + 1

    # the names :mod:`.pangu_ultra_moe`'s routing reads
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor


def describe(cfg: LagunaConfig) -> Dict[str, Any]:
    """The widths and counts a reader of ``stats()`` needs to turn this
    family's counters into bytes and operations, under the published
    names (``stats()["model"]``)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if f.name not in ("dtype", "param_dtype")}
    out.update(dtype=jnp.dtype(cfg.dtype).name,
               layer_types=list(cfg.layer_types),
               num_attention_heads_per_layer=list(
                   cfg.num_attention_heads_per_layer),
               n_full_layers=cfg.n_full, n_sliding_layers=cfg.n_sliding)
    return out


def health(counters: Dict[str, int], cfg: LagunaConfig) -> Dict:
    """``health_snapshot()["family"]``: the share of (token, pick) pairs on
    experts held here, the fullest held expert's rows over the mean, the
    mean cache tokens a query lane read in a layer of each kind, and the
    window groups' share of the blocks held; each None before the first
    dispatch that feeds it."""
    total, local = (counters.get("moe_pairs_total"),
                    counters.get("moe_pairs_local"))
    lanes = total / cfg.num_experts_per_tok / max(
        cfg.n_sparse, 1) if total else 0
    held = counters.get("kv_blocks_in_use_sum")

    def mean_read(name, layers):
        return (round(counters.get(name, 0) / layers / lanes, 1)
                if lanes and layers else None)

    return {
        "local_pair_pct": round(100.0 * local / total, 2) if total else None,
        "load_max_over_mean": (
            round(counters["moe_rows_max"] * cfg.n_local_experts / local, 3)
            if local else None),
        "full_tokens_read_a_lane": mean_read("full_tokens_read", cfg.n_full),
        "window_tokens_read_a_lane": mean_read("window_tokens_read",
                                               cfg.n_sliding),
        "window_block_pct": (
            round(100.0 * counters.get("kv_window_blocks_in_use_sum", 0)
                  / held, 2) if held else None)}


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: LagunaConfig, kind: str, sparse: bool
                  ) -> Dict[str, tuple]:
    E, D, Hk = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    H = cfg.heads_full if kind == FULL else cfg.heads_sliding
    shapes = {"ln_in": (E,), "ln_post": (E,), "wq": (E, H * D),
              "wkv": (E, 2 * Hk * D),     # [keys | values]: one matmul
              "wo": (H * D, E)}
    if cfg.head_gate:
        shapes["wg"] = (E, H)
    if not sparse:
        I = cfg.intermediate_size
        shapes.update(w_gate=(E, I), w_up=(E, I), w_down=(I, E))
    else:
        I, Is = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        shapes.update(router=(E, cfg.num_experts),
                      # the held experts: [gate | up] side by side, so one
                      # grouped matmul makes both
                      w_gu=(cfg.n_local_experts, E, 2 * I),
                      w_down=(cfg.n_local_experts, I, E))
        if Is:
            shapes.update(ws_gate=(E, Is), ws_up=(E, Is), ws_down=(Is, E))
    return shapes


def _parts(cfg: LagunaConfig):
    """``(path, stacked-over, kind, sparse)`` of each stacked tree of the
    parameters, in the stack's order."""
    P, S, T = cfg.n_periods, cfg.slides_per_period, cfg.n_tail
    parts = [(("first",), (), FULL, not cfg.first_layer_dense)]
    if P:
        parts += [(("periods", "slide"), (P, S), SLIDING, True),
                  (("periods", "full"), (P,), FULL, True)]
    if T:
        parts.append((("tail",), (T,), SLIDING, True))
    return parts


def init_params(cfg: LagunaConfig, key: jax.Array) -> Dict:
    """``{"embed", "ln_f", "lm_head", "first": layer 0, "periods":
    {"slide": [P, S, ..], "full": [P, ..]}, "tail": [T, ..]}`` (``tail``
    only where the depth leaves one); norms at one, matrices normal with
    variance ``1 / fan_in``."""
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 64))

    def dense(shape, rows):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w * float(rows) ** -0.5).astype(pd)

    E, V = cfg.hidden_size, cfg.vocab_size
    out = {"embed": dense((V, E), 1.0), "ln_f": jnp.ones((E,), pd),
           "lm_head": dense((E, V), E)}
    for path, lead, kind, sparse in _parts(cfg):
        tree = {}
        for name, shape in _layer_shapes(cfg, kind, sparse).items():
            full = tuple(lead) + shape
            tree[name] = (jnp.ones(full, pd) if name.startswith("ln")
                          else dense(full, shape[-2]))
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = tree
    return out


def num_params(cfg: LagunaConfig) -> int:
    n = 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    for _, lead, kind, sparse in _parts(cfg):
        n += math.prod(lead) * sum(
            math.prod(s) for s in _layer_shapes(cfg, kind, sparse).values())
    return n


# ---------------------------------------------------------------------------
# the layer's parts
# ---------------------------------------------------------------------------

def _norm(x, w, cfg):
    return _rms_norm(x, w, cfg.rms_norm_eps, False)


def rope_inv_freq(cfg: LagunaConfig, kind: str):
    """``(inv_freq [r / 2] float32, factor)`` of one kind of layer, None
    for a kind with no rotary embedding; ``r`` is the rotated lanes.
    Sliding: ``theta ** (-2i / r)``. Full: none (``full_rope_type``
    ``"none"``), or YaRN as
    ``transformers`` computes it: the plain and the ``/ factor``
    frequencies blended by a ramp between the lanes that turn
    ``beta_fast`` and ``beta_slow`` times in the original context, and
    cosine and sine scaled by ``attention_factor``."""
    if kind == SLIDING:
        r = int(cfg.head_dim * cfg.sliding_rotary_factor)
        i = jnp.arange(0, r, 2, dtype=jnp.float32)
        return cfg.sliding_rope_theta ** (-i / r), 1.0
    if cfg.full_rope_type == "none":
        return None
    r = int(cfg.head_dim * cfg.full_rotary_factor)
    theta, L0 = cfg.full_rope_theta, cfg.yarn_original_max_position
    extra = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    inter = extra / cfg.yarn_factor

    def dim(turns):
        return r * math.log(L0 / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim(cfg.yarn_beta_slow)), r - 1)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low) /
                    max(high - low, 0.001), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp), cfg.yarn_attention_factor


def _rope_tables(cfg, kind, pos):
    """``cos, sin [.., r / 2]`` (float32) at integer positions ``pos``;
    None for a kind of layer with no rotary embedding."""
    freq = rope_inv_freq(cfg, kind)
    if freq is None:
        return None
    inv, factor = freq
    ang = pos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def _rope(x, cos, sin):
    """Rotate pairs ``(i, i + r / 2)`` of the first ``r = 2 x cos.shape[-1]``
    lanes of ``x [T, H, D]``; the other lanes pass through."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    c, s = cos[:, None], sin[:, None]
    y = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)
    return y.astype(x.dtype)


def _project(lp, a, rope, cfg):
    """``a [T, E]`` (normed) -> ``q [T, H, D]``, ``k``, ``v [T, Hk, D]``
    (rotated where ``rope`` is not None) and the gate ``z [T, H]``
    (float32; None without one)."""
    dt, D, Hk = cfg.dtype, cfg.head_dim, cfg.num_key_value_heads
    T = a.shape[0]
    q = (a @ lp["wq"].astype(dt)).reshape(T, -1, D)
    kv = (a @ lp["wkv"].astype(dt)).reshape(T, 2 * Hk, D)
    z = (jax.nn.sigmoid((a @ lp["wg"].astype(dt)).astype(jnp.float32))
         if "wg" in lp else None)
    if rope is None:
        return q, kv[:, :Hk], kv[:, Hk:], z
    return _rope(q, *rope), _rope(kv[:, :Hk], *rope), kv[:, Hk:], z


def _gated_out(lp, o, z, cfg):
    """``concat_h(z_h o_h) W_o`` of ``o [T, H, D]`` (``concat_h(o_h) W_o``
    where ``z`` is None)."""
    if z is not None:
        o = (o.astype(jnp.float32) * z[..., None]).astype(cfg.dtype)
    return o.reshape(o.shape[0], -1) @ lp["wo"].astype(cfg.dtype)


def _head(params, x, cfg):
    x = _norm(x, params["ln_f"], cfg)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def _walk(params, cfg, x, carry, layer):
    """The stack's order over ``layer(x, carry, lp, kind, slide_rank,
    full_rank, experts, in_stack) -> (x, carry)``: layer 0, a scan over the
    periods, the tail. ``experts`` are a part's routed experts still
    stacked (``[layers, G, K, N]``, read at ``in_stack``), kept out of
    every scan's operands: sliced a layer, each would be copied whole."""
    S = cfg.slides_per_period
    x, carry = layer(x, carry, params["first"], FULL, None, 0, {}, None)

    def split(tree, lead):
        experts = {k: tree[k].reshape((-1,) + tree[k].shape[lead:])
                   for k in ("w_gu", "w_down")}
        return {k: v for k, v in tree.items() if k not in experts}, experts

    if cfg.n_periods:
        slide, s_experts = split(params["periods"]["slide"], 2)
        full, f_experts = split(params["periods"]["full"], 1)

        def period(c, xs):
            (x, carry), (sl, fl, p) = c, xs
            for j in range(S):
                x, carry = layer(x, carry, {k: v[j] for k, v in sl.items()},
                                 SLIDING, p * S + j, None, s_experts,
                                 p * S + j)
            return layer(x, carry, fl, FULL, None, 1 + p, f_experts, p), None

        (x, carry), _ = lax.scan(
            period, (x, carry),
            (slide, full, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    if cfg.n_tail:
        tail, t_experts = split(params["tail"], 1)
        first = cfg.n_periods * S

        def one(c, xs):
            lp, t = xs
            return layer(*c, lp, SLIDING, first + t, None, t_experts, t), None

        (x, carry), _ = lax.scan(
            one, (x, carry),
            (tail, jnp.arange(cfg.n_tail, dtype=jnp.int32)))
    return x, carry


# ---------------------------------------------------------------------------
# whole sequences, no cache: for the tests
# ---------------------------------------------------------------------------

def forward(params: Dict, ids, cfg: LagunaConfig, use_kernel: bool = False):
    """``ids [B, S] -> logits [B, S, V]`` (float32), no cache."""
    B, S = ids.shape
    T = B * S
    x = jnp.take(params["embed"], ids.reshape(-1), axis=0).astype(cfg.dtype)
    pos = jnp.tile(jnp.arange(S), B)
    ropes = {kind: _rope_tables(cfg, kind, pos) for kind in (FULL, SLIDING)}
    real = jnp.ones((T,), bool)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    masks = {FULL: j <= i,
             SLIDING: (j <= i) & (j > i - cfg.sliding_window)}

    def layer(x, carry, lp, kind, slide_rank, full_rank, experts, in_stack):
        r = x if cfg.router_before_attention else None
        a = _norm(x, lp["ln_in"], cfg)
        q, k, v, z = _project(lp, a, ropes[kind], cfg)
        o = _masked_sdpa(
            q.reshape(B, S, -1, cfg.head_dim), k.reshape(B, S, -1,
                                                         cfg.head_dim),
            v.reshape(B, S, -1, cfg.head_dim),
            jnp.broadcast_to(masks[kind], (B, S, S)))
        x = x + _gated_out(lp, o.reshape(T, -1, cfg.head_dim), z, cfg)
        f, _ = _ffn({**lp, **experts}, _norm(x, lp["ln_post"], cfg), real,
                    cfg, use_kernel, in_stack, r)
        return x + f, carry

    x, _ = _walk(params, cfg, x, (), layer)
    return _head(params, x, cfg).reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the paged pool and its groups
# ---------------------------------------------------------------------------

def validate_serving(cfg: LagunaConfig, serving_config) -> None:
    """What the paged engine offers and this family does not serve is an
    error at construction, never a silent fall-back."""
    sc = serving_config
    off = [name for name, on in (
        ("lora_slots", sc.lora_slots), ("kv_quant", sc.kv_quant),
        ("quantize", sc.quantize), ("tp > 1", sc.tp > 1),
        ("spec_decode", sc.spec_decode),
        ("prefix_cache", sc.prefix_cache), ("offload", sc.offload)) if on]
    if off:
        raise ValueError(
            f"the laguna family does not serve with {off}: its paged "
            f"programs take bf16/fp32 weights and one pool on one device, "
            f"have no verify step, and a window group's blocks behind the "
            f"window are gone, so no prefix of a sequence can be served "
            f"from the cache or from the host")
    if not sc.prefill_chunk or sc.prefill_chunk > cfg.window_chunk:
        raise ValueError(
            f"the laguna family's window rings hold sliding_window + "
            f"window_chunk = {cfg.sliding_window} + {cfg.window_chunk} "
            f"tokens: prefill_chunk must be set and at most "
            f"{cfg.window_chunk}, got {sc.prefill_chunk}")


def paged_cache_groups(cfg: LagunaConfig, block_size: int
                       ) -> Tuple[Optional[int], ...]:
    """The cache groups, one entry each in the order a table row lays them
    out: ``None`` for the full layers' group (a table entry a block of the
    sequence), a ring's entries for each window group."""
    return (None,) + (cfg.ring_blocks(block_size),) * cfg.window_groups


def mixed_lane_budget(cfg: LagunaConfig, max_slots: int) -> int:
    """The query lanes ONE wave of the packed mixed step holds. The engine
    gives a step its decode rows and, oldest first, the prompts' chunks
    that fit beside them, so a step is one wave however many prompts are
    in prefill: a second wave streams every held weight and runs both
    attention kernels over all rows again for the few lanes it carries."""
    return _WAVE_ROWS * max_slots


def init_paged_pool(cfg: LagunaConfig, num_blocks: int, block_size: int,
                    dtype=None, kv_quant=None, mesh=None) -> Dict:
    """``{"k", "v": [layers a group, num_blocks, block_size, Hk, D]}``:
    block ``b`` holds 16 positions of every layer of WHICHEVER group took
    it. Block 0 is the null block, as in every pool of the engine."""
    if kv_quant is not None or mesh is not None:
        raise ValueError("the laguna pool is neither quantized nor sharded")
    dt = dtype if dtype is not None else cfg.dtype
    shape = (cfg.n_full, num_blocks, block_size, cfg.num_key_value_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_pool_block_bytes(cfg: LagunaConfig, block_size: int, dtype=None,
                           kv_quant=None, tp: int = 1) -> int:
    """Bytes one physical block costs: a group's layers, keys and values."""
    if kv_quant is not None or tp != 1:
        raise ValueError("the laguna pool is neither quantized nor sharded")
    dt = dtype if dtype is not None else cfg.dtype
    return (cfg.n_full * int(block_size) * cfg.num_key_value_heads * 2 *
            cfg.head_dim * jnp.dtype(dt).itemsize)


# ---------------------------------------------------------------------------
# paged serving: one forward over query lanes, attention over rows
# ---------------------------------------------------------------------------

def _ring_positions(hi, R: int, bs: int):
    """``[M, R x bs]``: the position each cell of a ring holds once
    position ``hi [M]`` is written: entry ``r`` holds the newest page ``p
    <= hi // bs`` with ``p % R == r`` (negative: none yet)."""
    last = hi // bs
    r = jnp.arange(R, dtype=jnp.int32)
    page = last[:, None] - jnp.mod(last[:, None] - r[None, :], R)
    return (page[:, :, None] * bs +
            jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(-1, R * bs)


def _attend_rows(q, pool, idx, tbl, start, dl, window, use_kernel):
    """Attention of rows ``q [M, Q, H, D]`` (row ``m``'s query ``i <=
    dl[m]`` at position ``start[m] + i``) over layer ``idx`` of the pool
    through ``tbl``: the full table ``[M, W]`` (``window`` None) or a
    window group's ring. The kernel, or in plain XLA (the kernel's oracle,
    and the path off the TPU) a gather of the same pages and one explicit
    mask."""
    M, Q, H, D = q.shape
    if use_kernel:
        from ..kernels.paged_attention import paged_attention
        if Q == 1:
            return paged_attention(q[:, 0], pool["k"], pool["v"], tbl, start,
                                   layer=idx, window=window)[:, None]
        return paged_attention(q, pool["k"], pool["v"], tbl, start,
                               draft_lens=dl, layer=idx, window=window)
    bs, Hk = pool["k"].shape[2:4]
    C = tbl.shape[1] * bs
    kk, vv = _kv_gather(pool, idx, tbl, M, C, Hk, D)
    edge = start[:, None] + jnp.minimum(jnp.arange(Q)[None, :], dl[:, None])
    if window is None:
        j = jnp.arange(C)[None, None, :]
        mask = j <= edge[:, :, None]
    else:
        j = _ring_positions(start + dl, tbl.shape[1], bs)[:, None, :]
        mask = (j <= edge[:, :, None]) & (j > edge[:, :, None] - window) & \
            (j >= 0)
    return _masked_sdpa(q, kk, vv, mask)


def _paged_rows(params, cfg, tokens, starts, n_row, block_tables, pool,
                use_kernel, wave_lanes=None):
    """The whole model over the rows ``tokens [M, Q]``: row ``m`` carries
    ``n_row[m]`` real tokens from position ``starts[m]`` on. Returns ``(x
    [M, E]`` after each row's LAST real token, pool, counters)``.

    ``wave_lanes=None``: all ``M x Q`` lanes in place, one pass (a decode
    step, a prefill). ``wave_lanes=Tw``: PACKED (the mixed step), the real
    lanes, row-major, in waves of ``Tw`` under a ``lax.while_loop``, each
    wave the whole stack against the pool; a wave scatters all its keys
    and values of a layer before any of its lanes attends there, and a
    row's lanes in one wave are at most ``min(Q, Tw)`` contiguous
    positions, which is what a ring is sized for."""
    M, Q = tokens.shape
    dt, D = cfg.dtype, cfg.head_dim
    bs = pool["k"].shape[2]
    R, G = cfg.ring_blocks(bs), cfg.window_groups
    Wf = block_tables.shape[1] - G * R           # the full group's entries
    packed = wave_lanes is not None
    Tw = wave_lanes if packed else M * Q
    Qa = min(Q, Tw)                    # the most lanes a row has in a wave
    n_row = n_row.astype(jnp.int32)
    ends = jnp.cumsum(n_row)
    n_real = ends[-1]
    begins = ends - n_row if packed else jnp.arange(M, dtype=jnp.int32) * Q
    full_tbl = block_tables[:, :Wf]

    def wave(w, pool):
        """The whole stack over wave ``w``'s lanes -> ``(x [Tw, E], pool,
        counters)``."""
        lo = w * Tw
        g = lo + jnp.arange(Tw, dtype=jnp.int32)
        if packed:
            row = jnp.minimum(jnp.searchsorted(ends, g, side="right"),
                              M - 1).astype(jnp.int32)
            real = g < n_real
            qi = jnp.where(real, g - begins[row], 0)
        else:
            row, qi = g // Q, g % Q
            real = qi < n_row[row]
        pos = starts[row] + qi
        page, off = pos // bs, pos % bs
        ropes = {kind: _rope_tables(cfg, kind, pos)
                 for kind in (FULL, SLIDING)}
        # the rows' view of the wave: row m has its lanes [a, a + n) here
        a = jnp.clip(lo - begins, 0, n_row) if packed else jnp.zeros_like(
            n_row)
        n = (jnp.clip(lo + Tw - begins, 0, n_row) - a) if packed else n_row
        att_start = jnp.where(n > 0, starts + a, 0)
        att_dl = jnp.maximum(n - 1, 0)
        # tokens a lane attends, and whole pages a row's call copies
        hi_page = (att_start + att_dl) // bs + 1
        lo_page = jnp.maximum(att_start - cfg.sliding_window + 1, 0) // bs
        read = {FULL: jnp.stack([
                    jnp.where(real, pos + 1, 0).sum(),
                    jnp.where(n > 0, hi_page * bs, 0).sum()]),
                SLIDING: jnp.stack([
                    jnp.where(real, jnp.minimum(
                        pos + 1, cfg.sliding_window), 0).sum(),
                    jnp.where(n > 0, (hi_page - lo_page) * bs, 0).sum()])}

        def to_rows(q):
            if not packed:
                return q.reshape(M, Q, *q.shape[1:])
            # row m's lanes are contiguous in the wave: M slices of Qa
            # lanes (past the wave's end: zeros no one reads)
            qp = jnp.concatenate([q, jnp.zeros((Qa,) + q.shape[1:], q.dtype)])
            return jax.vmap(lambda s: lax.dynamic_slice_in_dim(qp, s, Qa))(
                jnp.clip(begins + a - lo, 0, Tw))

        def to_lanes(o):
            if not packed:
                return o.reshape((Tw,) + o.shape[2:])
            o = o.reshape((M * Qa,) + o.shape[2:])[
                row * Qa + jnp.clip(qi - a[row], 0, Qa - 1)]
            return jnp.where(real[:, None, None], o, 0)

        def layer(x, carry, lp, kind, slide_rank, full_rank, experts,
                  in_stack):
            pool, counts = carry
            if kind == FULL:
                idx, tbl, window = full_rank, full_tbl, None
                col = jnp.minimum(page, Wf - 1)
            else:
                group, idx = slide_rank // cfg.n_full, slide_rank % cfg.n_full
                first = Wf + group * R
                tbl = lax.dynamic_slice_in_dim(block_tables, first, R, axis=1)
                window, col = cfg.sliding_window, first + page % R
            phys = jnp.where(real, block_tables[row, col], 0)
            r = x if cfg.router_before_attention else None
            q, k, v, z = _project(lp, _norm(x, lp["ln_in"], cfg),
                                  ropes[kind], cfg)
            pool, _, _ = _kv_store(pool, idx, phys, off, k, v)
            o = _attend_rows(to_rows(q), pool, idx, tbl, att_start, att_dl,
                             window, use_kernel)
            x = x + _gated_out(lp, to_lanes(o), z, cfg)
            f, moe = _ffn({**lp, **experts}, _norm(x, lp["ln_post"], cfg),
                          real, cfg, use_kernel, in_stack, r)
            return x + f, (pool, counts.at[:4].add(moe).at[
                jnp.array(_READ_AT[kind])].add(read[kind]))

        x = jnp.take(params["embed"], tokens[row, qi], axis=0).astype(dt)
        counts = jnp.zeros((len(PAGED_COUNTERS),), jnp.int32).at[-1].set(Tw)
        x, (pool, counts) = _walk(params, cfg, x, (pool, counts), layer)
        return x, pool, counts

    # the lane of each row's last real token
    last = jnp.maximum(ends - 1 if packed else begins + n_row - 1, 0)
    if not packed:
        x, pool, counts = wave(0, pool)
        return x[last], pool, counts

    def step(carry):
        w, pool, x_last, counts = carry
        x, pool, c = wave(w, pool)
        mine = (n_row > 0) & (last // Tw == w)
        return (w + 1, pool, jnp.where(mine[:, None], x[last % Tw], x_last),
                counts + c)

    _, pool, x_last, counts = lax.while_loop(
        lambda carry: carry[0] * Tw < n_real, step,
        (jnp.int32(0), pool, jnp.zeros((M, cfg.hidden_size), dt),
         jnp.zeros((len(PAGED_COUNTERS),), jnp.int32)))
    return x_last, pool, counts


def _no_lora(lora):
    if lora is not None:
        raise ValueError("the laguna family serves no adapters")


def paged_prefill(params: Dict, cfg: LagunaConfig, ids, prompt_lens,
                  block_tables, pool: Dict, active, lora=None,
                  use_kernel: bool = False):
    """``generation.paged_prefill``'s contract: ``ids [B, Sb]``
    right-padded prompts with no cached prefix -> (next-token logits ``[B,
    V]`` at ``prompt_lens - 1``, pool, counters)."""
    _no_lora(lora)
    x, pool, counts = _paged_rows(
        params, cfg, ids, jnp.zeros_like(prompt_lens),
        jnp.where(active, prompt_lens, 0), block_tables, pool, use_kernel)
    return _head(params, x, cfg), pool, counts


def paged_decode_step(params: Dict, cfg: LagunaConfig, tokens, seq_lens,
                      block_tables, pool: Dict, active,
                      use_kernel: bool = False, lora=None):
    """``generation.paged_decode_step``'s contract: one token a slot at
    position ``seq_lens`` -> (logits ``[M, V]``, pool, counters)."""
    _no_lora(lora)
    x, pool, counts = _paged_rows(
        params, cfg, tokens[:, None], seq_lens, active.astype(jnp.int32),
        block_tables, pool, use_kernel)
    return _head(params, x, cfg), pool, counts


def paged_mixed_step(params: Dict, cfg: LagunaConfig, tokens, starts,
                     q_lens, block_tables, pool: Dict, active,
                     use_kernel: bool = False, lora=None):
    """``generation.paged_mixed_step``'s contract: row ``m`` carries
    ``q_lens[m]`` real tokens from position ``starts[m]`` on (one for a
    decoding slot, a chunk for a prompt in prefill) -> (logits ``[M, V]``
    after each row's last real token, pool, counters). Packed: only the
    real lanes are computed, in waves of :func:`mixed_lane_budget` lanes
    (one wave where the engine kept the step inside that budget)."""
    _no_lora(lora)
    M, Q = tokens.shape
    x, pool, counts = _paged_rows(
        params, cfg, tokens, starts,
        jnp.where(active, jnp.clip(q_lens, 0, Q), 0), block_tables, pool,
        use_kernel, wave_lanes=min(M * Q, mixed_lane_budget(cfg, M)))
    return _head(params, x, cfg), pool, counts
