"""Autoregressive generation with a KV cache — TPU decode done the XLA way.

Capability target: the reference ecosystem's ``generate()`` surface
(PaddleNLP ``generation_utils.py`` — greedy / sampling with top-k/top-p,
eos handling, ragged prompt batches; SURVEY §2.6 ecosystem row).

TPU redesign, not a translation:

* **One compiled program.** Prefill + the whole decode loop run inside a
  single ``jax.jit`` — the decode loop is a ``lax.while_loop`` over token
  steps with an ALIVE-MASK EARLY EXIT (a batch whose rows all hit eos at
  step k pays k steps, not max_new_tokens; greedy outputs stay
  bit-identical because skipped steps would only have emitted pad), so
  there is no per-token Python dispatch (the reference's per-token Python
  loop is exactly the pattern SURVEY §3.1 warns against on TPU).
* **Static cache layout.** The KV cache is a stacked ``[L, B, C, Hk, D]``
  pytree with a *static* capacity ``C = prompt_len + max_new_tokens``; every
  decode step writes at a uniform scalar index via
  ``lax.dynamic_update_slice`` — no dynamic shapes anywhere, so XLA keeps the
  whole loop on-device and updates the cache in place (buffer reuse inside
  the program; the streaming API additionally donates the cache across
  dispatches).
* **Left-aligned ragged batches.** Ragged prompts are left-padded
  internally: every row's last prompt token then sits at the same index, the
  prefill's final-position logits are a plain ``h[:, -1]`` slice, and decode
  writes land at one scalar index for all rows (a right-padded layout would
  need per-row scatter indices).
* **Streaming tier.** :class:`DecodeSession` exposes prefill/step as two
  jitted functions with the cache DONATED between dispatches, for callers
  that need a token at a time (``inference.Predictor`` wiring, speculative
  clients). Same kernels, same cache layout.
* **Paged tier.** :func:`init_paged_pool` / :func:`paged_prefill` /
  :func:`paged_decode_step` are the block-table attention entry points the
  continuous-batching serving engine drives (``inference.serving``,
  docs/SERVING.md): one physical block pool shared by every slot,
  gather-based attention over each sequence's own blocks, token-level
  bit-parity with the dense cache path pinned by tests/test_serving.py.
  The pool is loop STATE of every paged program, carried whole through
  the layer scan and written and read at the layer's index
  (:func:`_layer_xs`): never a scanned operand, which would be a second
  buffer.

MoE caveat: GShard routing capacity is evaluated per forward call, so a
decode step routes B tokens in isolation while a full no-cache forward
routes B*S jointly — when capacity DROPS occur the two paths can diverge
(both are "correct" MoE inference; drops are a training-throughput knob).
Exact greedy parity with the full-forward oracle therefore holds when no
tokens are dropped, which is the regime inference runs in (per-step load
of B tokens over E experts rarely exceeds capacity).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .llama import (LlamaConfig, _masked_sdpa, _mm, _moe_ffn, _rms_norm,
                    _rope)
from .lora import lora_delta

__all__ = ["GenerationConfig", "init_cache", "prefill", "decode_step",
           "make_generate_fn", "generate", "DecodeSession",
           "init_paged_pool", "paged_pool_block_bytes", "paged_pool_specs",
           "paged_prefill", "paged_decode_step",
           "paged_spec_step", "paged_mixed_step", "sample_tokens",
           "seed_key",
           "validate_sampling", "validate_tp",
           "PAGED_COUNTERS", "validate_serving", "describe", "health"]


# ---------------------------------------------------------------------------
# sampling-knob config (the ONE struct shared by every decode tier)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GenerationConfig:
    """Sampling knobs (ref: PaddleNLP GenerationConfig).

    The single source of truth for every decode tier: the functional
    :func:`generate`, the eager ``LlamaForCausalLM.generate`` kwargs
    surface, ``inference.GenerationPredictor``, and the serving engine
    (``inference.serving``) all resolve through this one struct — the two
    previously-duplicated knob sets (``inference.generation``'s class vs
    the eager wrapper's kwargs) are gone.
    """

    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    # the PRNG seed every sampling tier resolves (the previously-hardcoded
    # jax.random.PRNGKey(0) default of the dense generate() path, folded
    # into the ONE config): dense generate derives its key from it when
    # the caller passes none, and the serving engine derives each
    # request's per-slot base key from it — outputs are reproducible per
    # (request, seed) across preemption, crash resubmit and failover
    seed: int = 0

    def replace(self, **kw) -> "GenerationConfig":
        return dataclasses.replace(self, **kw)

    # knobs for which None is a VALUE (disable), not the unset spelling
    _NONEABLE = frozenset({"top_k", "top_p", "eos_token_id"})

    @classmethod
    def resolve(cls, generation_config: Optional["GenerationConfig"] = None,
                **overrides) -> "GenerationConfig":
        """Merge a kwargs surface onto an optional base config. The string
        ``"unset"`` always means "not given" (keeps the base's field; the
        same sentinel ``ServingEngine.submit`` uses). For the Optional
        knobs (``top_k``/``top_p``/``eos_token_id``) ``None`` is a real
        override — ``eos_token_id=None`` disables EOS even when the base
        config sets one; for every other field ``None`` means "not given"
        (None is never a valid value for them, e.g. ``pad_token_id=None``
        keeps the base's pad id)."""
        base = generation_config if generation_config is not None else cls()
        updates = {k: v for k, v in overrides.items()
                   if not (isinstance(v, str) and v == "unset")
                   and not (v is None and k not in cls._NONEABLE)}
        return dataclasses.replace(base, **updates) if updates else base


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_cache(cfg: LlamaConfig, batch: int, capacity: int,
               dtype=None) -> Dict:
    """Stacked KV cache ``{"k","v": [L, B, C, Hk, D]}`` (static capacity)."""
    dt = dtype if dtype is not None else cfg.dtype
    shape = (cfg.num_hidden_layers, batch, capacity, cfg.kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _cached_layer(lp: Dict, x, ck, cv, cos, sin, kv_mask, write_idx,
                  cfg: LlamaConfig):
    """One decoder block attending against the cache.

    ``x [B, T, E]`` (T = prompt length for prefill, 1 for decode);
    ``ck/cv [B, C, Hk, D]`` this layer's cache; ``kv_mask [B, T, C]`` True
    where query t may attend key position j; ``write_idx`` scalar — the new
    K/V rows are written at cache positions [write_idx, write_idx+T).
    Returns ``(y, ck, cv)``. MoE configs also apply the routed FFN (aux loss
    is irrelevant at inference and dropped).
    """
    B, T, E = x.shape
    H, Hk, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype

    h = _rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps, cfg.use_fused_norm)
    q = _mm(h, lp, "wq", dt).reshape(B, T, H, D)
    k = _mm(h, lp, "wk", dt).reshape(B, T, Hk, D)
    v = _mm(h, lp, "wv", dt).reshape(B, T, Hk, D)
    q = _rope(q, cos, sin, False)
    k = _rope(k, cos, sin, False)

    ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, write_idx, 0, 0))
    cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, write_idx, 0, 0))

    o = _masked_sdpa(q, ck, cv, kv_mask)
    x = x + _mm(o.reshape(B, T, H * D).astype(dt), lp, "wo", dt)

    x, drops = _ffn_tail(lp, x, cfg)
    return x, ck, cv, drops


def _ffn_tail(lp: Dict, x, cfg: LlamaConfig):
    """The post-attention half of a decoder block on ``x [B, T, E]``:
    pre-norm + dense SwiGLU or the routed MoE FFN. Returns
    ``(block output, dropped_tokens)``."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps, cfg.use_fused_norm)
    if cfg.moe_num_experts:
        y, _, drops = _moe_ffn(lp, h, cfg)
        return x + y, drops
    g = jax.nn.silu(_mm(h, lp, "w_gate", dt)) * _mm(h, lp, "w_up", dt)
    return x + _mm(g, lp, "w_down", dt), jnp.float32(0.0)


def _lm_head(params: Dict, cfg: LlamaConfig, x):
    """Final norm + LM head on the last-position hidden ``x [B, 1, E]`` ->
    fp32 logits ``[B, V]`` (shared by the dense and paged cache paths)."""
    x = _rms_norm(x, params["ln_f"], cfg.rms_norm_eps, cfg.use_fused_norm)
    if cfg.tie_word_embeddings:
        logits = (x @ params["embed"].T.astype(cfg.dtype))[:, 0]
    else:
        logits = _mm(x, params, "lm_head", cfg.dtype)[:, 0]
    return logits.astype(jnp.float32)


def _fwd_cached(params: Dict, cfg: LlamaConfig, ids, cache: Dict, cos, sin,
                kv_mask, write_idx):
    """Embed ``ids [B, T]``, run all layers against the cache (lax.scan over
    the stacked [L, ...] params+cache), return (last-position logits [B, V],
    new cache)."""
    x = jnp.take(params["embed"], ids, axis=0).astype(cfg.dtype)

    def body(h, xs):
        lp, ck, cv = xs
        h, ck, cv, drops = _cached_layer(lp, h, ck, cv, cos, sin, kv_mask,
                                         write_idx, cfg)
        return h, (ck, cv, drops)

    x, (ck, cv, drops) = lax.scan(body, x, (params["layers"], cache["k"],
                                            cache["v"]))
    logits = _lm_head(params, cfg, x[:, -1:])
    return logits, {"k": ck, "v": cv}, drops.sum()


def _row_tables(cfg: LlamaConfig, pos):
    """Per-row RoPE tables for positions ``pos [B, T]`` -> cos/sin [B,T,D]."""
    from ..kernels.rope import rope_cos_sin
    T = pos.shape[1]
    mk = jax.vmap(functools.partial(rope_cos_sin, T, cfg.head_dim,
                                    cfg.rope_theta))
    return mk(position_ids=pos)


def left_align(ids, prompt_lens, pad_token_id: int = 0):
    """Right-padded rows -> left-padded (row b's tokens end at index S-1)."""
    B, S = ids.shape
    shift = (S - prompt_lens)[:, None]
    src = (jnp.arange(S)[None, :] - shift) % S
    out = jnp.take_along_axis(ids, src, axis=1)
    return jnp.where(jnp.arange(S)[None, :] >= shift, out, pad_token_id)


def prefill(params: Dict, cfg: LlamaConfig, ids, prompt_lens, cache: Dict,
            left_padded: bool = False):
    """Run the prompt through the model, filling cache positions [0, S).

    ``ids [B, S]`` is RIGHT-padded ragged (the public convention) unless
    ``left_padded=True``; rows are left-aligned internally so every row's
    last prompt token sits at index S-1 (see module docstring). Returns
    (next-token logits [B, V], cache, dropped_tokens) — the last is the
    in-graph MoE capacity-drop count (0.0 for dense configs; r4 VERDICT
    next #10).
    """
    if not left_padded:
        ids = left_align(ids, prompt_lens)
    B, S = ids.shape
    C = cache["k"].shape[2]
    shift = S - prompt_lens                                  # [B] pad amount
    valid = jnp.arange(S)[None, :] >= shift[:, None]         # [B, S]
    pos = jnp.maximum(jnp.arange(S)[None, :] - shift[:, None], 0)
    cos, sin = _row_tables(cfg, pos)
    causal = jnp.arange(C)[None, :] <= jnp.arange(S)[:, None]  # [S, C]
    valid_k = jnp.pad(valid, ((0, 0), (0, C - S)))             # [B, C]
    kv_mask = causal[None] & valid_k[:, None, :]
    logits, cache, drops = _fwd_cached(params, cfg, ids, cache, cos, sin,
                                       kv_mask, 0)
    return logits, cache, drops


def decode_step(params: Dict, cfg: LlamaConfig, token, t, prompt_lens,
                prompt_pad, cache: Dict):
    """One decode step: ``token [B]`` at step ``t`` (0-based), writing cache
    position ``S + t`` (``prompt_pad = S`` the left-padded prompt length).
    Returns (logits [B, V], cache, dropped_tokens)."""
    C = cache["k"].shape[2]
    pos = (prompt_lens + t)[:, None]                         # [B, 1]
    cos, sin = _row_tables(cfg, pos)
    j = jnp.arange(C)[None, :]
    valid_prompt = (j >= (prompt_pad - prompt_lens)[:, None]) & (j < prompt_pad)
    appended = (j >= prompt_pad) & (j <= prompt_pad + t)
    kv_mask = (valid_prompt | appended)[:, None, :]          # [B, 1, C]
    return _fwd_cached(params, cfg, token[:, None], cache, cos, sin,
                       kv_mask, prompt_pad + t)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def _sample(logits, key, temperature: float, top_k: Optional[int],
            top_p: Optional[float]):
    """Greedy when ``temperature == 0``; else temperature/top-k/top-p
    sampling (static config -> a fixed compiled program per setting)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        top_k = min(top_k, logits.shape[-1])
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (the token
        # that crosses the threshold stays in)
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def seed_key(seed: int):
    """The raw uint32[2] PRNG base key for one seed — pure host
    arithmetic (the threefry key packing ``[seed >> 32, seed & 0xffffffff]``),
    so the serving engine can stamp per-request base keys into its slot
    table without a device dispatch per submit. The per-token key for
    sample index ``t`` is ``jax.random.fold_in(seed_key(seed), t)`` —
    a pure function of ``(seed, t)``, which is what makes sampled streams
    reproducible per ``(request, seed)`` across preemption-recompute,
    crash resubmit, cross-replica failover AND speculative verify (the
    verify samples index ``t`` with exactly the key the sequential step
    would have used)."""
    import numpy as np
    s = int(seed)
    return np.array([(s >> 32) & 0xffffffff, s & 0xffffffff], np.uint32)


def validate_sampling(g: "GenerationConfig") -> None:
    """Structured validation of the sampling knobs a serving submit may
    carry — rejects only genuinely unsupported combinations, naming the
    supported surface (the ``ServingEngine.submit`` contract)."""
    import math as _math
    ok = True
    t = g.temperature
    if t is None or not _math.isfinite(float(t)) or float(t) < 0:
        ok = False
    if g.top_k is not None and int(g.top_k) < 1:
        ok = False
    if g.top_p is not None and not (0.0 < float(g.top_p) <= 1.0):
        ok = False
    if not ok:
        raise ValueError(
            f"unsupported sampling config (temperature={g.temperature!r}, "
            f"top_k={g.top_k!r}, top_p={g.top_p!r}); supported knobs: "
            f"temperature >= 0 (0 = greedy argmax), top_k >= 1 or None "
            f"(disabled), top_p in (0, 1] or None (disabled), integer "
            f"seed")


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Per-row sampling with DEVICE operands — the serving tier's sampler.

    ``logits [B, V]`` fp32; ``keys [B, 2]`` uint32 per-row PRNG keys
    (already folded to the row's sample index); ``temperature [B]`` fp32;
    ``top_k [B]`` int32 (``0`` disables); ``top_p [B]`` fp32 (``1.0``
    disables — and genuinely keeps the full distribution, see below).
    Every knob is a runtime operand, so ONE compiled program serves every
    request mix — the static-arg :func:`_sample` above compiles one
    program per knob setting and stays the dense ``generate()`` tier's
    spelling.

    Rows with ``temperature == 0`` return ``jnp.argmax(logits)`` selected
    through a ``jnp.where`` — BIT-IDENTICAL to the greedy path, so every
    greedy parity oracle (kernel-vs-gather, int8, prefix-hit, resubmit)
    extends unchanged. Boundary semantics match :func:`_sample` exactly:
    top-p keeps the smallest prefix of the sorted distribution whose
    cumulative mass reaches ``p`` (the crossing token stays IN; a token
    whose preceding cumulative mass already equals ``p`` exactly is out),
    and ``top_p=1.0`` keeps every positive-probability token.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # branchless per-row knobs: greedy rows run the sampling math on a
    # safe temperature and are overridden by the final where
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / t
    srt = jnp.sort(scaled, axis=-1)[..., ::-1]           # descending
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)   # 0 = disabled
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p over the top-k-surviving tail (the same composition order as
    # _sample): entries below the kth VALUE drop out of the sorted view
    # first — a value threshold, not a positional cut, so ties at the
    # k-th rank survive into the top-p stage exactly as in _sample
    srt = jnp.where(srt >= kth, srt, -jnp.inf)
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p = jnp.clip(top_p, 0.0, 1.0)[:, None]
    keep = cum - probs < p
    cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    masked = jnp.where(masked < cutoff, -jnp.inf, masked)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temperature <= 0.0, greedy,
                     sampled.astype(jnp.int32))


# ---------------------------------------------------------------------------
# generate: prefill + scan decode in ONE compiled program
# ---------------------------------------------------------------------------

def make_generate_fn(cfg: LlamaConfig, *, max_new_tokens: int,
                     temperature: float = 0.0, top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     eos_token_id: Optional[int] = None,
                     pad_token_id: int = 0, return_drops: bool = False):
    """Build ``gen(params, ids [B,S], prompt_lens [B], key) -> tokens
    [B, max_new_tokens]`` — jit it once, every call is one device program.

    ``ids`` may be right-padded; rows are left-aligned internally (see module
    docstring). Rows finish at ``eos_token_id`` and emit ``pad_token_id``
    thereafter.
    """

    def gen(params, ids, prompt_lens, key):
        B, S = ids.shape
        C = S + max_new_tokens
        ids_l = left_align(ids, prompt_lens, pad_token_id)

        cache = init_cache(cfg, B, C)
        logits, cache, drops0 = prefill(params, cfg, ids_l, prompt_lens,
                                        cache, left_padded=True)

        # first token comes from the prefill logits; subsequent tokens from
        # decode steps 0..max_new-2 (eos itself is emitted, pad thereafter)
        key, sub = jax.random.split(key)
        tok0 = _sample(logits, sub, temperature, top_k, top_p)
        done0 = (jnp.zeros((B,), bool) if eos_token_id is None
                 else tok0 == eos_token_id)

        # decode loop: a lax.while_loop (not scan) so the program EXITS as
        # soon as every row has hit eos — a batch that finishes at step k
        # pays k steps, not max_new_tokens (the alive-mask early exit).
        # Greedy outputs are bit-identical to the full-length scan: the
        # output buffer is pre-filled with pad_token_id, which is exactly
        # what the skipped steps would have emitted for all-done rows.
        def body(carry):
            t, tok, cache, done, key, drops, out = carry
            logits, cache, d = decode_step(params, cfg, tok, t, prompt_lens,
                                           jnp.int32(S), cache)
            key, sub = jax.random.split(key)
            nxt = _sample(logits, sub, temperature, top_k, top_p)
            nxt = jnp.where(done, pad_token_id, nxt).astype(ids.dtype)
            ndone = done if eos_token_id is None else \
                done | (nxt == eos_token_id)
            out = lax.dynamic_update_slice(out, nxt[:, None], (0, t + 1))
            return (t + 1, nxt, cache, ndone, key, drops + d, out)

        def cond(carry):
            t, _, _, done, _, _, _ = carry
            return (t < max_new_tokens - 1) & ~done.all()

        if max_new_tokens > 1:
            out0 = jnp.full((B, max_new_tokens), pad_token_id, ids.dtype)
            out0 = lax.dynamic_update_slice(
                out0, tok0[:, None].astype(ids.dtype), (0, 0))
            carry = (jnp.int32(0), tok0.astype(ids.dtype), cache, done0, key,
                     drops0, out0)
            _, _, _, _, _, drops, out = lax.while_loop(cond, body, carry)
        else:
            drops = drops0
            out = tok0[:, None].astype(ids.dtype)
        if return_drops:
            return out, drops
        return out

    return gen


def generate(params: Dict, ids, cfg: LlamaConfig, *, max_new_tokens: int,
             prompt_lens=None, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None, pad_token_id: int = 0,
             seed: Optional[int] = None,
             key: Optional[jax.Array] = None):
    """Fixed-batch decode convenience wrapper: jit-cached by (cfg,
    sampling knobs, shapes).

    This is the DENSE-cache tier — every row holds a ``[B, max_seq]`` KV
    cache for its whole lifetime and the batch retires together (with the
    in-graph all-EOS early exit). Serving traffic with mixed lengths,
    shared prefixes, or admission churn belongs on
    ``inference.serving.ServingEngine`` / ``GenerationPredictor.serve``,
    whose ``ServingConfig.prefix_cache`` / ``prefill_chunk`` / ``preempt``
    knobs add paged on-demand KV, automatic prefix caching, and chunked
    prefill while staying bit-identical to this path under greedy
    decoding — this function doubles as that parity oracle in the tests.

    Sampling randomness resolves through ``seed`` (default: the
    ``GenerationConfig.seed`` default, 0 — the previously-hardcoded
    ``PRNGKey(0)``); an explicit ``key`` overrides it."""
    ids = jnp.asarray(ids)
    B, S = ids.shape
    if prompt_lens is None:
        prompt_lens = jnp.full((B,), S, jnp.int32)
    else:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(int(seed) if seed is not None
                                 else GenerationConfig.seed)
    fn = _jitted_gen(cfg, max_new_tokens, temperature, top_k, top_p,
                     eos_token_id, pad_token_id)
    return fn(params, ids, prompt_lens, key)


_GEN_CACHE: Dict = {}


def _jitted_gen(cfg: LlamaConfig, max_new_tokens, temperature, top_k, top_p,
                eos_token_id, pad_token_id):
    # LlamaConfig is a plain (unhashable) dataclass; key the jit cache by its
    # full repr + the sampling knobs. jax.jit's own cache handles shapes.
    key = (repr(cfg), max_new_tokens, temperature, top_k, top_p,
           eos_token_id, pad_token_id)
    if key not in _GEN_CACHE:
        fn = make_generate_fn(
            cfg, max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
            pad_token_id=pad_token_id)
        _GEN_CACHE[key] = jax.jit(fn)
    return _GEN_CACHE[key]


# ---------------------------------------------------------------------------
# streaming decode (cache donated across dispatches)
# ---------------------------------------------------------------------------

class DecodeSession:
    """Token-at-a-time decoding for streaming callers (Predictor wiring).

    Two jitted programs — prefill and step — with the cache DONATED on every
    dispatch, so XLA updates it in place instead of allocating a fresh
    [L, B, C, Hk, D] buffer per token.

        sess = DecodeSession(params, cfg, capacity=512)
        logits = sess.prefill(ids, prompt_lens)   # fills the cache
        for _ in range(n):
            tok = logits.argmax(-1)
            logits = sess.step(tok)
    """

    def __init__(self, params: Dict, cfg: LlamaConfig, capacity: int):
        self.params, self.cfg, self.capacity = params, cfg, capacity
        self._cache = None
        self._t = 0

        def _prefill(params, ids, plens, cache):
            return prefill(params, cfg, ids, plens, cache)

        def _step(params, tok, t, plens, ppad, cache):
            return decode_step(params, cfg, tok, t, plens, ppad, cache)

        self._jpre = jax.jit(_prefill, donate_argnums=(3,))
        self._jstep = jax.jit(_step, donate_argnums=(5,))
        self._dropped = None

    def prefill(self, ids, prompt_lens=None):
        ids = jnp.asarray(ids)
        B, S = ids.shape
        if S > self.capacity:
            raise ValueError(f"prompt {S} exceeds capacity {self.capacity}")
        self._plens = (jnp.full((B,), S, jnp.int32) if prompt_lens is None
                       else jnp.asarray(prompt_lens, jnp.int32))
        self._ppad = jnp.int32(S)
        self._t = 0
        cache = init_cache(self.cfg, B, self.capacity)
        logits, self._cache, drops = self._jpre(self.params, ids,
                                                self._plens, cache)
        self._dropped = drops
        return logits

    def step(self, token):
        if self._cache is None:
            raise RuntimeError("call prefill() first")
        if int(self._ppad) + self._t >= self.capacity:
            raise RuntimeError(f"capacity {self.capacity} exhausted")
        logits, self._cache, drops = self._jstep(
            self.params, jnp.asarray(token), jnp.int32(self._t),
            self._plens, self._ppad, self._cache)
        self._dropped = self._dropped + drops
        self._t += 1
        return logits

    @property
    def dropped_tokens(self) -> float:
        """Cumulative in-graph MoE capacity-drop count for this session
        (always 0.0 for dense configs; nonzero means decode may diverge
        from the full-forward oracle — the checkable form of the module
        docstring's MoE caveat; r4 VERDICT next #10)."""
        return float(self._dropped) if self._dropped is not None else 0.0


# ---------------------------------------------------------------------------
# paged KV cache (block-table attention — the serving-engine entry points)
# ---------------------------------------------------------------------------

def validate_tp(cfg: LlamaConfig, tp: int) -> None:
    """Structured validation of a serving tensor-parallel degree against a
    model config (the same error convention as :func:`validate_sampling` /
    ``llama.validate_quant_mode``): the paged pool shards its kv-heads
    axis, so ``tp`` must divide ``num_kv_heads`` — checked HERE, up front,
    instead of failing deep inside ``device_put`` on an indivisible
    ``Hk``. Raised at ``ServingConfig``/engine construction."""
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tensor-parallel degree must be >= 1 (1 = the "
                         f"single-device engine), got tp={tp}")
    if tp == 1:
        return
    Hk = cfg.kv_heads
    if Hk % tp:
        divisors = [d for d in range(1, Hk + 1) if Hk % d == 0]
        raise ValueError(
            f"tensor-parallel degree tp={tp} does not divide the model's "
            f"num_kv_heads={Hk} (the paged KV pool shards its kv-heads "
            f"axis); supported degrees for this config: {divisors}")


def _merge_heads(o, cfg: LlamaConfig):
    """Flatten attention output ``[B, T, h, D] -> [B, T, h*D]`` for the
    output projection. Under serving tensor parallelism (``cfg.tp_axis``
    set — the engine's shard_map'd programs) ``h`` is the LOCAL head
    slice: all_gather the shards into the full head set first. The gather
    is a pure tiled concatenation — no floating-point addition — so the
    merged tensor is BITWISE the single-device one and the replicated
    wo/FFN/lm-head math downstream stays inside every greedy/seeded
    parity oracle. (A Megatron row-parallel merge — psum of per-shard
    ``wo`` partials — would change fp accumulation order and break
    bit-parity vs TP=1; measured on XLA:CPU.)"""
    if cfg.tp_axis is not None:
        o = lax.all_gather(o, cfg.tp_axis, axis=2, tiled=True)
    B, T = o.shape[:2]
    return o.reshape(B, T, o.shape[2] * o.shape[3])


def _local_heads(cfg: LlamaConfig, pool: Dict) -> Tuple[int, int]:
    """(query heads, kv heads) of the pool VIEW a paged entry point was
    handed. Under shard_map the pool leaf is this shard's ``Hk/tp`` head
    slice, and the GQA group size ``G = H // Hk`` is shard-invariant — so
    the local query-head count follows from the pool shape and the config
    keeps its global head counts (``cfg.head_dim`` stays correct, being
    derived from the UNCHANGED hidden_size / num_attention_heads)."""
    Hk = pool["k"].shape[3]
    return Hk * (cfg.num_attention_heads // cfg.kv_heads), Hk


# The rest of what the serving engine asks of a family's module
# (``models.paged_family``), for the family this module serves: every engine
# feature is served and there is nothing to describe. What one dispatch
# counts on the device, in this order (int32, summed over a decode
# dispatch's iterations): lanes run through the model's per-token parts,
# real or not (a prefill's bucket, a decode step's slots, a verify step's
# ``M x Q``, a mixed step's waves x their lanes), and the waves of a mixed
# step.
PAGED_COUNTERS = ("lanes_computed", "mixed_waves")
# lanes of one wave of a packed mixed step, in multiples of the slots
_WAVE_ROWS = 16


def _lane_counts(lanes, waves=0):
    return jnp.stack([jnp.asarray(lanes, jnp.int32),
                      jnp.asarray(waves, jnp.int32)])


def validate_serving(cfg: LlamaConfig, serving_config) -> None:
    return None


def describe(cfg: LlamaConfig) -> None:
    return None


def health(counters: Dict, cfg: LlamaConfig) -> Dict:
    """``health_snapshot()["family"]``: the two device counters as they
    stand (docs/OPS.md says how to read them)."""
    return {name: int(counters.get(name, 0)) for name in PAGED_COUNTERS}


def paged_pool_specs(pool: Dict, mesh, axis: str = "tp") -> Dict:
    """PartitionSpecs splitting every pool leaf's kv-heads axis over mesh
    ``axis``: K/V ``[L, N, bs, Hk, D]`` and scale ``[L, N, bs, Hk]``
    leaves both shard dim 3, so int8 pools shard k/v and their scale
    planes identically and a shard's scales always describe its own
    blocks. Block ids stay GLOBAL — tables and slot operands replicate,
    only pool bytes split. Indivisible head counts raise the structured
    :func:`~paddle_tpu.distributed.sharding.shard_dim_spec` error naming
    the leaf."""
    from ..distributed.sharding import shard_dim_spec
    return {name: shard_dim_spec(leaf.shape, mesh, axis, dim=3,
                                 name=f"paged_pool.{name}")
            for name, leaf in pool.items()}


def init_paged_pool(cfg: LlamaConfig, num_blocks: int, block_size: int,
                    dtype=None, kv_quant=None, mesh=None,
                    tp_axis: str = "tp") -> Dict:
    """Physical KV block pool ``{"k","v": [L, num_blocks, block_size, Hk,
    D]}`` shared by every sequence the serving engine runs (PagedAttention
    layout): a sequence holds only the blocks its block table points at,
    so HBM scales with tokens actually in flight instead of
    ``max_slots * max_seq``. Physical block 0 is reserved as the NULL
    block — the scatter target for masked lanes (padded prefill positions,
    retired slots) — and is never handed out by the block manager
    (``inference.serving.paged_cache``).

    ``kv_quant="int8"`` stores K/V as int8 with PER-TOKEN-PER-HEAD fp32
    scales alongside (``{"k","v": int8, "k_scale","v_scale": [L, N, bs,
    Hk]}``): each KV entry quantizes independently at write time, so
    incremental decode scatters never re-quantize a block, preemption
    recompute reproduces bit-identical int8 entries, and the prefix cache
    shares quantized blocks exactly like fp ones (content keys hash token
    ids, not bytes). At ~``(D+4)/(4*D)`` the bytes of an fp32 pool this
    multiplies usable blocks at a fixed byte budget ~3.5x — more
    concurrent sequences, more cached prefixes, more preemption headroom.
    Dequantization happens inside the consumers (fused into the Pallas
    kernel's block loads; the XLA gather fallback dequantizes after its
    gather) — a dense fp copy of the pool never exists.

    With ``mesh`` given (a ``tp_mesh`` — serving tensor parallelism,
    ISSUE 12) every leaf is emitted with a ``NamedSharding`` splitting its
    kv-heads axis over ``tp_axis`` (:func:`paged_pool_specs`): each device
    holds ``Hk/tp`` heads of every block, so per-device KV bytes per token
    divide by the TP degree while block ids, tables and the host-side
    block manager stay device-count-agnostic. int8 pools shard k/v and
    their scale planes identically.
    """
    from .llama import KV_QUANT_MODES, validate_quant_mode
    validate_quant_mode(kv_quant, KV_QUANT_MODES, "kv_quant")
    dt = dtype if dtype is not None else cfg.dtype
    shape = (cfg.num_hidden_layers, num_blocks, block_size, cfg.kv_heads,
             cfg.head_dim)
    if kv_quant == "int8":
        pool = {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    else:
        pool = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if mesh is not None:
        from jax.sharding import NamedSharding
        specs = paged_pool_specs(pool, mesh, tp_axis)
        pool = {n: jax.device_put(a, NamedSharding(mesh, specs[n]))
                for n, a in pool.items()}
    return pool


def paged_pool_block_bytes(cfg: LlamaConfig, block_size: int, dtype=None,
                           kv_quant=None, tp: int = 1) -> int:
    """Bytes ONE physical block costs across all layers (K + V + scales) —
    the capacity-planning arithmetic behind sizing ``num_blocks`` to a
    byte budget. ``tp > 1`` returns the
    PER-DEVICE cost of the block under a tensor-parallel pool: each
    device holds ``Hk/tp`` heads of every block, so a fixed per-device
    byte budget backs ``tp`` times the blocks."""
    import numpy as _np
    validate_tp(cfg, tp)
    L, bs = cfg.num_hidden_layers, int(block_size)
    Hk, D = cfg.kv_heads // int(tp), cfg.head_dim
    if kv_quant == "int8":
        return L * bs * Hk * (2 * D * 1 + 2 * 4)
    dt = dtype if dtype is not None else cfg.dtype
    return L * bs * Hk * 2 * D * _np.dtype(dt).itemsize


def _kv_quantize(x):
    """Symmetric per-token-per-head int8: ``x [..., Hk, D]`` fp ->
    ``(q int8 [..., Hk, D], scale fp32 [..., Hk])`` with ``x ~= q *
    scale``. Non-finite inputs (a poisoned request's NaN K/V) yield NaN
    scales, so dequantized reads stay NaN — quantization never LAUNDERS
    poison into plausible values; containment stays with the attention
    mask exactly as on fp pools."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _kv_store(p: Dict, layer, phys, off, k, v):
    """Scatter freshly computed ``k``/``v [..., Hk, D]`` into the WHOLE
    pool at ``(layer, phys, off)`` (quantizing when the pool is int8): an
    in-place write of the new entries into leaves ``[L, N, bs, Hk, D]``
    that a layer scan carries, never a layer's slab sliced out and put
    back. Returns ``(new_pool, k_attend, v_attend)`` — the attend pair is
    what LATER READS of these entries will observe (identity for fp pools,
    the int8 round-trip for quantized ones), so the batched prefill can
    attend exactly the values decode will gather back and every engine
    path sees ONE consistent view of a KV entry."""
    at = (layer, phys, off)
    if "k_scale" in p:
        qk, sk = _kv_quantize(k)
        qv, sv = _kv_quantize(v)
        out = {"k": p["k"].at[at].set(qk), "v": p["v"].at[at].set(qv),
               "k_scale": p["k_scale"].at[at].set(sk),
               "v_scale": p["v_scale"].at[at].set(sv)}
        return out, qk.astype(jnp.float32) * sk[..., None], \
            qv.astype(jnp.float32) * sv[..., None]
    return {"k": p["k"].at[at].set(k.astype(p["k"].dtype)),
            "v": p["v"].at[at].set(v.astype(p["v"].dtype))}, k, v


def _kv_gather(p: Dict, layer, block_tables, B: int, C: int, Hk: int,
               D: int):
    """Gather layer ``layer`` of the pool through the block tables into
    logical order ``[B, C, Hk, D]``, dequantizing int8 pools after the
    gather — the XLA FALLBACK path (``_masked_sdpa`` consumes the result).
    The Pallas kernel (``kernels.paged_attention``) never materializes
    this."""
    kk = p["k"][layer, block_tables].reshape(B, C, Hk, D)
    vv = p["v"][layer, block_tables].reshape(B, C, Hk, D)
    if "k_scale" in p:
        ks = p["k_scale"][layer, block_tables].reshape(B, C, Hk)
        vs = p["v_scale"][layer, block_tables].reshape(B, C, Hk)
        kk = kk.astype(jnp.float32) * ks[..., None]
        vv = vv.astype(jnp.float32) * vs[..., None]
    return kk, vv


def _layer_xs(params: Dict, lora: Optional[Dict]):
    """Scan xs for one paged forward pass: the stacked layer weights, each
    layer's index, and — when multi-adapter LoRA serving is on — the
    stacked adapter-pool leaves (``lora["layers"]``, sliced per layer
    alongside the weights; see ``models.lora``), else ``None``: no leaf,
    which keeps the traced computation that of the pre-LoRA engine — the
    zero-cost-for-base-traffic contract. The POOL is not among them: a
    scan's ``xs`` and ``ys`` are other buffers than its argument (a
    layer's slab sliced out and written back, the stack copied whole), so
    the pool rides in the scan's carry and is written and read at the
    layer's index."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    return (params["layers"], jnp.arange(L, dtype=jnp.int32),
            None if lora is None else lora["layers"])


def paged_prefill(params: Dict, cfg: LlamaConfig, ids, prompt_lens,
                  block_tables, pool: Dict, active, lora=None,
                  use_kernel: bool = False):
    """Prefill a BATCH of admitted sequences into the paged pool
    (``use_kernel`` is the family interface's: a prompt with no cached
    prefix attends over itself here and reads no pool, so it is unused).

    ``ids [B, Sb]`` right-padded to the (power-of-2 bucketed) length
    ``Sb``; ``prompt_lens [B]`` the real token counts; ``block_tables
    [B, W]`` each row's physical block ids (logical position ``j`` lives
    in block ``table[j // block_size]`` at offset ``j % block_size``);
    ``active [B]`` bool — the admission step pads the batch dim to the
    engine's ``max_slots`` so prefill executables are bounded by the
    BUCKET count alone, and inactive pad rows scatter into the null block.
    Right-padding keeps RoPE positions at the plain ``0..Sb-1`` table and
    the causal mask makes each row's pad tail invisible to its real
    positions; pad-position K/V also scatter into the null block. On int8
    pools the attention reads the QUANTIZED round-trip of this chunk's
    K/V (``_kv_store``'s attend view), so prefill attends exactly the
    values decode/chunk dispatches will later gather — cold and
    prefix-hit requests see one consistent quantized history. ``lora``
    (optional) is the multi-adapter operand ``{"ids": [B] int32 slot
    ids, "layers": stacked adapter pool}`` — a device operand like the
    sampling knobs, so adapter churn never retraces (``models.lora``).
    Returns (next-token logits ``[B, V]`` read at each row's
    ``prompt_len - 1``, pool, counters: ``PAGED_COUNTERS``).
    """
    from ..kernels.rope import rope_cos_sin
    B, Sb = ids.shape
    H, Hk = _local_heads(cfg, pool)    # the shard's head slice under TP
    D = cfg.head_dim
    bs = pool["k"].shape[2]
    W = block_tables.shape[1]
    dt = cfg.dtype
    cos, sin = rope_cos_sin(Sb, D, cfg.rope_theta)
    j = jnp.arange(Sb)
    valid = (j[None, :] < prompt_lens[:, None]) & active[:, None]   # [B, Sb]
    phys = jnp.where(valid, block_tables[:, jnp.minimum(j // bs, W - 1)], 0)
    off = jnp.broadcast_to(j % bs, (B, Sb))
    kv_mask = jnp.broadcast_to((j[None, :] <= j[:, None])[None],
                               (B, Sb, Sb))             # causal per row

    x = jnp.take(params["embed"], ids, axis=0).astype(dt)

    def body(carry, xs):
        h, pool = carry
        lp, layer, ll = xs
        hh = _rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps, cfg.use_fused_norm)
        q = _mm(hh, lp, "wq", dt)
        k = _mm(hh, lp, "wk", dt)
        v = _mm(hh, lp, "wv", dt)
        if ll is not None:
            lids = lora["ids"]
            q = q + lora_delta(hh, ll["qA"], ll["qB"], lids, dt)
            k = k + lora_delta(hh, ll["kA"], ll["kB"], lids, dt)
            v = v + lora_delta(hh, ll["vA"], ll["vB"], lids, dt)
        q = q.reshape(B, Sb, H, D)
        k = k.reshape(B, Sb, Hk, D)
        v = v.reshape(B, Sb, Hk, D)
        q = _rope(q, cos, sin, False)
        k = _rope(k, cos, sin, False)
        pool, ka, va = _kv_store(pool, layer, phys, off, k, v)
        o = _masked_sdpa(q, ka, va, kv_mask)
        m = _merge_heads(o, cfg).astype(dt)
        d = _mm(m, lp, "wo", dt)
        if ll is not None:
            d = d + lora_delta(m, ll["oA"], ll["oB"], lora["ids"], dt)
        h = h + d
        return (_ffn_tail(lp, h, cfg)[0], pool), None

    (x, pool), _ = lax.scan(body, (x, pool), _layer_xs(params, lora))
    idx = jnp.maximum(prompt_lens - 1, 0)[:, None, None]
    last = jnp.take_along_axis(x, idx, axis=1)          # [B, 1, E]
    return _lm_head(params, cfg, last), pool, _lane_counts(B * Sb)


def paged_decode_step(params: Dict, cfg: LlamaConfig, tokens, seq_lens,
                      block_tables, pool: Dict, active,
                      use_kernel: bool = False, lora=None):
    """One decode iteration over ``M`` serving slots against the block pool.

    ``tokens [M]`` the last sampled token per slot; ``seq_lens [M]`` the KV
    entries already written (= the new token's position); ``block_tables
    [M, W]``; ``active [M]`` bool — inactive slots (empty, retired, past
    their budget) scatter their K/V into the null block and their logits
    are garbage the scheduler ignores. Attention reads each slot's own
    blocks and masks positions ``> seq_len``, through one of two paths:

    * ``use_kernel=False`` — the XLA gather fallback: ``pool[block_tables]``
      materializes the ``[M, W*bs, Hk, D]`` logical view (dequantized for
      int8 pools), then ``_masked_sdpa`` runs the masked softmax. The
      reference oracle, and the runtime path off-TPU by default.
    * ``use_kernel=True`` — the Pallas flash-decoding kernel
      (:func:`paddle_tpu.kernels.paged_attention`): block tables are
      consumed inside the kernel (each live K/V page copied once for
      every kv head, int8 dequant fused in), split-K over cells of pages
      with the online-softmax merge. No gather is ever materialized — the
      long-context bandwidth win. STATIC: bake it per compiled program
      (``ServingConfig.paged_kernel`` / ``FLAGS_serving_paged_kernel``).

    Returns (logits ``[M, V]``, pool, counters).
    """
    M = tokens.shape[0]
    H, Hk = _local_heads(cfg, pool)    # the shard's head slice under TP
    D = cfg.head_dim
    bs = pool["k"].shape[2]
    W = block_tables.shape[1]
    C = W * bs
    dt = cfg.dtype
    cos, sin = _row_tables(cfg, seq_lens[:, None])       # [M, 1, D]
    widx = jnp.minimum(seq_lens // bs, W - 1)
    phys = jnp.where(active,
                     jnp.take_along_axis(block_tables, widx[:, None],
                                         axis=1)[:, 0], 0)
    off = seq_lens % bs
    jj = jnp.arange(C)[None, :]
    kv_mask = (jj <= seq_lens[:, None])[:, None, :]      # [M, 1, C]

    x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(dt)

    def body(carry, xs):
        h, pool = carry
        lp, layer, ll = xs
        hh = _rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps, cfg.use_fused_norm)
        q = _mm(hh, lp, "wq", dt)
        k = _mm(hh, lp, "wk", dt)
        v = _mm(hh, lp, "wv", dt)
        if ll is not None:
            lids = lora["ids"]
            q = q + lora_delta(hh, ll["qA"], ll["qB"], lids, dt)
            k = k + lora_delta(hh, ll["kA"], ll["kB"], lids, dt)
            v = v + lora_delta(hh, ll["vA"], ll["vB"], lids, dt)
        q = q.reshape(M, 1, H, D)
        k = k.reshape(M, 1, Hk, D)
        v = v.reshape(M, 1, Hk, D)
        q = _rope(q, cos, sin, False)
        k = _rope(k, cos, sin, False)
        pool, _, _ = _kv_store(pool, layer, phys, off, k[:, 0], v[:, 0])
        if use_kernel:
            from ..kernels.paged_attention import paged_attention
            o = paged_attention(q[:, 0], pool["k"], pool["v"], block_tables,
                                seq_lens, k_scale=pool.get("k_scale"),
                                v_scale=pool.get("v_scale"),
                                layer=layer)[:, None]
        else:
            kk, vv = _kv_gather(pool, layer, block_tables, M, C, Hk, D)
            o = _masked_sdpa(q, kk, vv, kv_mask)
        m = _merge_heads(o, cfg).astype(dt)
        d = _mm(m, lp, "wo", dt)
        if ll is not None:
            d = d + lora_delta(m, ll["oA"], ll["oB"], lora["ids"], dt)
        h = h + d
        return (_ffn_tail(lp, h, cfg)[0], pool), None

    (x, pool), _ = lax.scan(body, (x, pool), _layer_xs(params, lora))
    return _lm_head(params, cfg, x), pool, _lane_counts(M)


def _mm_qkv(hh, lp, dt):
    """``wq``, ``wk``, ``wv`` as ONE matmul against their concatenation
    (int8 weights with their scales). Not for the matmul's sake: the TPU
    compiler wants these three weights in another layout, and under a loop
    around the layer scan it re-lays the whole stack before the loop (805
    MB at 7B widths, resident for the program's length); the concatenation
    is a layer's copy in whatever layout it likes, 48 MB."""
    names = ("wq", "wk", "wv")
    w = {"w": jnp.concatenate([lp[n] for n in names], axis=-1)}
    if "wq_s" in lp:
        w["w_s"] = jnp.concatenate([lp[n + "_s"] for n in names], axis=-1)
    nq, nk = lp["wq"].shape[-1], lp["wk"].shape[-1]
    return jnp.split(_mm(hh, w, "w", dt), [nq, nq + nk], axis=-1)


def _lm_head_all(params: Dict, cfg: LlamaConfig, x):
    """Final norm + LM head over EVERY position of ``x [B, T, E]`` ->
    fp32 logits ``[B, T, V]`` — the speculative verify needs one
    next-token distribution per drafted position, not just the last."""
    x = _rms_norm(x, params["ln_f"], cfg.rms_norm_eps, cfg.use_fused_norm)
    if cfg.tie_word_embeddings:
        logits = x @ params["embed"].T.astype(cfg.dtype)
    else:
        logits = _mm(x, params, "lm_head", cfg.dtype)
    return logits.astype(jnp.float32)


def paged_spec_step(params: Dict, cfg: LlamaConfig, tokens, seq_lens,
                    draft_lens, block_tables, pool: Dict, active,
                    use_kernel: bool = False, lora=None):
    """Speculative VERIFY over ``M`` serving slots: one multi-query decode
    iteration per slot against the block pool.

    ``tokens [M, Q]`` — row ``m`` holds ``[t0, d1, .., d_k, pad..]``: the
    slot's last sampled token followed by ``draft_lens[m] <= Q - 1``
    drafted tokens (pad lanes repeat a real token — finite by
    construction, and their K/V scatter is masked to the null block);
    ``seq_lens [M]`` — KV entries already committed (= ``t0``'s write
    position, exactly :func:`paged_decode_step`'s contract); ``active
    [M]`` bool. The step writes K/V for positions ``seq_lens + q`` for
    every valid query ``q <= draft_lens`` and returns logits for each:
    ``logits[m, q]`` is the next-token distribution AFTER
    ``tokens[m, :q+1]`` — verifying draft ``d_{q+1}`` against the token
    sampled from ``logits[m, q]`` reproduces the sequential decode stream
    exactly (query ``q`` attends ``j <= seq_lens[m] + q``: committed KV
    plus the in-pass draft prefix, the same set the sequential step at
    that position would see; on int8 pools the attention reads the
    QUANTIZED round-trip of the in-pass writes).

    The engine rolls back on rejection HOST-SIDE: positions past the
    accepted prefix hold stale draft KV that the next dispatch's write at
    the new ``seq_len`` overwrites (position ``seq_len``) or the
    ``j <= seq_len`` mask hides (beyond), and surplus BLOCKS return to
    the ref-counted manager via the preemption free path. Garbage query
    rows (``q > draft_lens[m]``) are unspecified and finite: the gather
    path attends them to the CAPPED window ``j <= seq_lens + draft_lens``,
    the kernel does not compute them and returns zeros there — either
    way the union of attendable positions never reaches unwritten block
    tails, so the poison-containment contract (``_masked_sdpa``/kernel
    V-zeroing) extends unchanged, and no caller reads those rows (the
    verify masks by ``draft_lens``; :func:`paged_mixed_step` takes row
    ``draft_lens``).

    ``use_kernel=True`` runs the Pallas flash-decoding kernel's
    multi-query entry point (:func:`paddle_tpu.kernels.paged_attention`
    with ``draft_lens``) — block tables consumed in-kernel, each live K/V
    page copied once and scored against the slot's ``draft_lens + 1``
    real query rows. Returns
    (logits ``[M, Q, V]``, pool, counters).

    The forward runs over all ``M x Q`` lanes IN PLACE (``Q = k + 1``,
    nearly every lane real): :func:`_paged_multiquery_forward` with no
    wave size."""
    x, pool, counts = _paged_multiquery_forward(
        params, cfg, tokens, seq_lens, draft_lens, block_tables, pool,
        active, use_kernel, lora)
    M, Q = tokens.shape
    return _lm_head_all(params, cfg, x.reshape(M, Q, -1)), pool, counts


def paged_mixed_step(params: Dict, cfg: LlamaConfig, tokens, starts,
                     q_lens, block_tables, pool: Dict, active,
                     use_kernel: bool = False, lora=None):
    """ONE mixed prefill+decode iteration over ``M`` serving slots: each
    row carries a per-row ROLE through two device operands, so role churn
    (which slots are mid-prefill vs decoding this step) never retraces.

    ``tokens [M, Q]`` — row ``m`` holds ``q_lens[m] <= Q`` real tokens
    (pad lanes repeat a real token); ``starts [M]`` — KV entries already
    committed for the row (``num_computed`` for a mid-prefill prompt,
    ``seq_len`` for a decoding slot). A decode slot is the ``q_lens ==
    1`` degenerate case — exactly :func:`paged_decode_step`'s computation;
    a prefill chunk is a ``q_lens == n`` row writing K/V for positions
    ``[starts, starts + n)`` with query ``q`` attending ``j <= starts +
    q`` — the causal window of a prefill from an offset. Both are
    the ``draft_lens = q_lens - 1`` specialization of the
    speculative-verify forward (:func:`paged_spec_step`), which is what
    this shares, so the kernel's multi-query entry and the gather oracle
    serve all three unchanged.

    **The step is packed** (:func:`_paged_multiquery_forward` with a wave
    size): of the ``M x Q`` lanes the engine hands over only the real ones
    go through the embedding, norms, projections, RoPE, the K/V scatter,
    ``wo``, the FFN and the LoRA deltas, in waves of ``min(M x Q,
    _WAVE_ROWS x M)`` lanes, one wave in steady state (a step's decoding
    slots and a chunk or two). Attention alone keeps the ``[M, Q]`` row
    view. The counters say what ran: ``lanes_computed`` = waves x their
    lanes, ``mixed_waves``.

    Returns ``(logits [M, V], pool, counters)`` where ``logits[m]`` is
    the next-token distribution after the row's LAST real token — a
    decode slot's next sample, or a prompt-completing chunk's FIRST
    token, sampled in the same dispatch that finished its prefill."""
    M, Q = tokens.shape
    x, pool, counts = _paged_multiquery_forward(
        params, cfg, tokens, starts, jnp.maximum(q_lens - 1, 0),
        block_tables, pool, active, use_kernel, lora,
        wave_lanes=min(M * Q, _WAVE_ROWS * M))
    return _lm_head(params, cfg, x[:, None]), pool, counts


def _paged_multiquery_forward(params: Dict, cfg: LlamaConfig, tokens,
                              seq_lens, draft_lens, block_tables,
                              pool: Dict, active, use_kernel: bool,
                              lora, wave_lanes: Optional[int] = None):
    """The multi-query decode iteration both :func:`paged_spec_step` and
    :func:`paged_mixed_step` are views of, as ONE forward over query
    LANES: lane ``t`` carries token ``tokens[row[t], q[t]]`` at position
    ``seq_lens[row[t]] + q[t]``. Everything that is per token — embedding,
    norms, ``wq``/``wk``/``wv``, RoPE, the K/V scatter, ``wo``, the FFN,
    LoRA deltas (per-lane adapter ids) — runs on ``[lanes, E]``; attention
    alone sees rows: the queries are laid into ``[M, Q, H, D]`` with the
    ``(start, draft_len)`` of the lanes each row has there, the kernel or
    the gather path runs as for any multi-query call, and the output rows
    are taken back to lanes. A real lane (``q <= draft_lens[row]`` of an
    active row) writes K/V at its position and attends ``j <=`` it; any
    other writes to the null block and is read by no one.

    Two callers set the lanes:

    * ``wave_lanes=None`` — all ``M x Q`` lanes IN PLACE, one pass (the
      verify step: nearly every lane is real). The row view is a reshape.
      Returns the hidden state of every lane, ``x [M x Q, E]``.
    * ``wave_lanes=Tw`` — PACKED (the mixed step): the real lanes, in
      row-major order, run in WAVES of ``Tw`` under a ``lax.while_loop``
      of ``ceil(real / Tw)`` trips, each wave the whole stack against the
      pool. A wave may cut a row's chunk; the row's lanes in one wave are
      a contiguous range of its positions, a lane's earlier positions sit
      in the same or an earlier wave, and a wave scatters all its K/V of a
      layer before any of its lanes attends there, so every position a
      lane attends is in the pool by then. Returns the hidden state of
      each row's LAST real lane, ``x [M, E]``.

    Returns ``(x, pool, counters)``."""
    M, Q = tokens.shape
    H, Hk = _local_heads(cfg, pool)    # the shard's head slice under TP
    D = cfg.head_dim
    bs = pool["k"].shape[2]
    W = block_tables.shape[1]
    C = W * bs
    dt = cfg.dtype
    packed = wave_lanes is not None
    Tw = wave_lanes if packed else M * Q
    Qa = min(Q, Tw)                    # the most lanes a row has in a wave
    n_row = jnp.where(active, jnp.clip(draft_lens + 1, 0, Q),
                      0).astype(jnp.int32)        # real lanes a row
    if packed:
        ends = jnp.cumsum(n_row)
        begins, n_real = ends - n_row, ends[-1]
    else:
        begins = jnp.arange(M, dtype=jnp.int32) * Q

    def wave(w, pool):
        """The whole stack over wave ``w``'s lanes -> ``(x [Tw, E],
        pool)``."""
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
        pos = seq_lens[row] + qi                         # [Tw] absolute
        cos, sin = _row_tables(cfg, pos[None])           # [1, Tw, D]
        phys = jnp.where(
            real, block_tables[row, jnp.minimum(pos // bs, W - 1)], 0)[None]
        off = (pos % bs)[None]
        # the rows' view of the wave: row m has its lanes [a, a + n) here
        a = jnp.clip(lo - begins, 0, n_row)
        n = jnp.clip(lo + Tw - begins, 0, n_row) - a
        att_start = jnp.where(n > 0, seq_lens + a, 0)
        att_dl = jnp.maximum(n - 1, 0)
        # the gather path's mask: query i attends j <= start + min(i,
        # draft_len), its committed KV plus the in-pass prefix; garbage
        # rows cap at draft_len so no row's mask ever reaches an unwritten
        # position
        kv_mask = None if use_kernel else jnp.arange(C)[None, None, :] <= (
            att_start[:, None] + jnp.minimum(
                jnp.arange(Qa)[None, :], att_dl[:, None]))[:, :, None]
        lids = lora["ids"][row] if lora is not None and packed else None

        def delta(hh, ll, name):
            la, lb = ll[name + "A"], ll[name + "B"]
            if packed:                 # an adapter id a lane
                return lora_delta(hh[0][:, None], la, lb, lids,
                                  dt)[:, 0][None]
            return lora_delta(hh.reshape(M, Q, -1), la, lb, lora["ids"],
                              dt).reshape(1, Tw, -1)

        def body(carry, xs):
            h, pool = carry
            lp, layer, ll = xs
            hh = _rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                           cfg.use_fused_norm)
            q, k, v = _mm_qkv(hh, lp, dt)
            if ll is not None:
                q = q + delta(hh, ll, "q")
                k = k + delta(hh, ll, "k")
                v = v + delta(hh, ll, "v")
            q = _rope(q.reshape(1, Tw, H, D), cos, sin, False)
            k = _rope(k.reshape(1, Tw, Hk, D), cos, sin, False)
            pool, _, _ = _kv_store(pool, layer, phys, off, k,
                                   v.reshape(1, Tw, Hk, D))
            if packed:
                # row m's lanes are contiguous in the wave: M slices of Qa
                # lanes (past the wave's end: zeros no one reads)
                qp = jnp.concatenate([q[0], jnp.zeros((Qa, H, D), q.dtype)])
                q = jax.vmap(lambda s: lax.dynamic_slice_in_dim(qp, s, Qa))(
                    jnp.clip(begins + a - lo, 0, Tw))
            else:
                q = q.reshape(M, Q, H, D)
            if use_kernel:
                from ..kernels.paged_attention import paged_attention
                o = paged_attention(q, pool["k"], pool["v"], block_tables,
                                    att_start, draft_lens=att_dl,
                                    k_scale=pool.get("k_scale"),
                                    v_scale=pool.get("v_scale"),
                                    layer=layer)
            else:
                kk, vv = _kv_gather(pool, layer, block_tables, M, C, Hk, D)
                o = _masked_sdpa(q, kk, vv, kv_mask)
            if packed:
                o = o.reshape(M * Qa, H, D)[
                    row * Qa + jnp.clip(qi - a[row], 0, Qa - 1)]
                o = jnp.where(real[:, None, None], o, 0)
            m = _merge_heads(o.reshape(1, Tw, H, D), cfg).astype(dt)
            d = _mm(m, lp, "wo", dt)
            if ll is not None:
                d = d + delta(m, ll, "o")
            return (_ffn_tail(lp, h + d, cfg)[0], pool), None

        x = jnp.take(params["embed"], tokens[row, qi], axis=0).astype(dt)
        (x, pool), _ = lax.scan(body, (x[None], pool),
                                _layer_xs(params, lora))
        return x[0], pool

    if not packed:
        x, pool = wave(0, pool)
        return x, pool, _lane_counts(M * Q)

    # the lane of each row's last real token, among the real lanes
    last = jnp.maximum(ends - 1, 0)

    def step(carry):
        w, pool, x_last = carry
        x, pool = wave(w, pool)
        mine = (n_row > 0) & (last // Tw == w)
        return w + 1, pool, jnp.where(mine[:, None], x[last % Tw], x_last)

    waves = (n_real + Tw - 1) // Tw
    _, pool, x_last = lax.while_loop(
        lambda carry: carry[0] < waves, step,
        (jnp.int32(0), pool, jnp.zeros((M, cfg.hidden_size), dt)))
    return x_last, pool, _lane_counts(waves * Tw, waves)
