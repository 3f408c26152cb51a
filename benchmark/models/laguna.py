"""Everything the benchmark knows of one family of model: the decoder that
``paddle_tpu.models.laguna`` computes (window and full attention layers
mixed, a head count a kind of layer, a per-head gate, a leading dense layer
and then routed experts beside a shared one, of which this chip holds a
share). A configuration names this file with ``"model": "laguna"``.

Three parts: the program's objects (its config and its seeded weights),
the counts of parameters, operations and cache bytes, and the operations
and bytes of the kernels the family runs, from its shapes and its
counters: what a roofline share is worked out from. All counts come from
the configuration file's keys or, for the readers, from the same keys as
the engine reports them in ``stats()["model"]`` (``describe``'s twin
widths: there ``num_experts`` is the router's width and
``n_local_experts`` the share; in the file ``num_experts`` is the share
and ``published_counts.num_experts`` the router's width). The per-layer
LIST keys stay as published, all their entries; entries ``[0,
num_hidden_layers)`` are the layers held. A multiply-add is two
operations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

FULL, SLIDING = "full_attention", "sliding_attention"


# ---------------------------------------------------------------------------
# the program's objects
# ---------------------------------------------------------------------------

def router_width(config: Dict[str, Any]) -> int:
    """Experts the router scores: the published count where the file holds
    a share, ``num_experts`` itself otherwise."""
    return int(config.get("published_counts", {}).get(
        "num_experts", config["num_experts"]))


def held_experts(config: Dict[str, Any]) -> int:
    return int(config.get("n_local_experts", config["num_experts"]))


def layers(config: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """``(kind, query heads, sparse)`` of each layer held."""
    n = int(config["num_hidden_layers"])
    dense = set(config.get("mlp_only_layers", [0]))
    return [(kind, int(h), i not in dense) for i, (kind, h) in enumerate(zip(
        config["layer_types"][:n],
        config["num_attention_heads_per_layer"][:n]))]


def program_config(config: Dict[str, Any], **program):
    import jax.numpy as jnp
    from paddle_tpu.models.laguna import LagunaConfig
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    for key in ("dtype", "param_dtype"):
        if key in program:
            program[key] = dtypes[program[key]]
    if list(config.get("mlp_only_layers", [0])) != [0] or \
            config.get("decoder_sparse_step", 1) != 1:
        raise ValueError("the laguna family has one leading dense layer "
                         "and routed experts in every other")
    n = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]
    full, slide = rope[FULL], rope[SLIDING]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise ValueError("full layers rotate with YaRN, sliding ones plain")
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_hidden_layers", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts_per_tok")
    return LagunaConfig(
        **{k: int(config[k]) for k in same},
        layer_types=tuple(config["layer_types"][:n]),
        num_attention_heads_per_layer=tuple(
            int(h) for h in config["num_attention_heads_per_layer"][:n]),
        num_experts=router_width(config),
        n_local_experts=int(config["num_experts"]),
        expert_offset=int(config.get("expert_offset", 0)),
        moe_routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        full_rope_theta=float(full["rope_theta"]),
        full_rotary_factor=float(full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=int(
            full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        sliding_rope_theta=float(slide["rope_theta"]),
        sliding_rotary_factor=float(slide["partial_rotary_factor"]),
        window_chunk=int(config.get("engine", {}).get("prefill_chunk", 128)),
        **program)


def vocab_size(config: Dict[str, Any]) -> int:
    """How many token ids the traffic may draw from: the rows held."""
    return int(config["vocab_size"])


def param_shapes(cfg):
    import jax
    from paddle_tpu.models import laguna as family
    return jax.eval_shape(functools.partial(family.init_params, cfg),
                          jax.random.key(0))


def make_weights(cfg, seed: int, shardings=None):
    """Seeded random weights on the device, in ONE jitted call, in the type
    they are stored in, laid out as the program lays them out: norms at
    one, every matrix normal with variance 1 / (rows it contracts over),
    the embedding at unit variance."""
    import jax
    import jax.numpy as jnp
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("ln"):
                leaves.append(jnp.ones(s.shape, s.dtype))
                continue
            rows = 1.0 if name == "embed" else float(s.shape[-2])
            w = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32) * rows ** -0.5
            leaves.append(w.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# counts, from the configuration file's keys
# ---------------------------------------------------------------------------

def attention_params(c: Dict[str, Any], heads: int) -> int:
    """Queries, keys and values, the per-head gate and the output of one
    layer with ``heads`` query heads."""
    E, D, Hk = (int(c["hidden_size"]), int(c["head_dim"]),
                int(c["num_key_value_heads"]))
    return E * heads * D + 2 * E * Hk * D + E * heads + heads * D * E


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def shared_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(
        c["shared_expert_intermediate_size"])


def layer_matmul_params(c: Dict[str, Any], heads: int, sparse: bool,
                        picks=None) -> float:
    """Matrices one layer holds HERE; with ``picks`` the routed experts a
    TOKEN meets here instead of those held."""
    E = int(c["hidden_size"])
    if not sparse:
        return attention_params(c, heads) + 3 * E * int(
            c["intermediate_size"])
    routed = held_experts(c) if picks is None else picks
    return (attention_params(c, heads) + E * router_width(c) +
            shared_params(c) + routed * expert_params(c))


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters held here that a token can be multiplied with: the
    layers' matrices and the output head (the embedding is a gather)."""
    return (sum(layer_matmul_params(c, h, sparse)
                for _, h, sparse in layers(c)) +
            int(c["hidden_size"]) * int(c["vocab_size"]))


def total_params(c: Dict[str, Any]) -> int:
    """Every parameter stored here."""
    E, V = int(c["hidden_size"]), int(c["vocab_size"])
    return matmul_params(c) + V * E + E + int(c["num_hidden_layers"]) * 2 * E


def active_matmul_params(c: Dict[str, Any], head: bool = True) -> float:
    """Parameters ONE token is multiplied with on this chip: attention,
    gate, router and shared expert of every layer, layer 0's dense FFN,
    and of the routed experts its picks that fall here
    (``num_experts_per_tok`` times the share of the experts held: uniform
    routing, which seeded random weights give)."""
    picks = int(c["num_experts_per_tok"]) * held_experts(c) / router_width(c)
    n = sum(layer_matmul_params(c, h, sparse, picks)
            for _, h, sparse in layers(c))
    return n + (int(c["hidden_size"]) * int(c["vocab_size"]) if head else 0)


def attention_flops_per_cache_token(c: Dict[str, Any], heads: int) -> int:
    """Operations of ONE query token against ONE cache token of ONE layer
    with ``heads`` query heads: a score and a weighted value a head."""
    return 4 * heads * int(c["head_dim"])


def serve_flops_per_token(c: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations one served token needs on this chip at a cache
    of ``context`` tokens (its own included): two a matmul parameter it
    meets, plus attention over ``context`` tokens in a full layer and over
    ``min(context, sliding_window)`` in a sliding one. ``head=False`` for
    a prompt token, whose logits are not made.

    **What ``mfu_pct.sat`` makes of it.** That reader works the context
    out from a counter named ``latent_tokens_read``, which this family
    does not count (it reports ``full_tokens_read`` and
    ``window_tokens_read``, under their own names), so it passes 0 and
    reads the MATMUL part alone (1.68 GFLOP a token with its logits at
    the published widths and this share): low by the attention over the
    cache, about a fifth at a context of 6k."""
    window = int(c["sliding_window"])
    attention = sum(
        attention_flops_per_cache_token(c, h) *
        (context if kind == FULL else min(context, window))
        for kind, h, _ in layers(c))
    return 2.0 * active_matmul_params(c, head) + attention


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations one token of a ``seq``-token causal
    sequence would need (no cell trains this family; the contract asks the
    count of every family): 6 a matmul parameter it meets, plus the
    attention at the mean context, times 3."""
    window = int(c["sliding_window"])
    attention = sum(
        attention_flops_per_cache_token(c, h) *
        (seq / 2 if kind == FULL else min(seq / 2, window))
        for kind, h, _ in layers(c))
    return 6.0 * active_matmul_params(c) + 3.0 * attention


def _token_bytes(c: Dict[str, Any], cache_bytes: int) -> int:
    """Keys and values of one token in one layer."""
    return (2 * int(c["num_key_value_heads"]) * int(c["head_dim"]) *
            cache_bytes)


def cache_bytes_per_token(c: Dict[str, Any], cache_bytes: int = 2) -> int:
    """What one more token ADDS to a sequence's cache: its keys and values
    in the FULL layers (3 x 4,096 B = 12,288 B at this cut). The sliding
    layers hold a bounded ring a SEQUENCE whatever its length: at most
    ``sliding_window + prefill_chunk`` tokens, rounded up to blocks plus
    one, in each (6 x 4,096 B x 656 = 16.1 MB a sequence here):
    ``window_cache_bytes_per_sequence``."""
    full = sum(1 for kind, _, _ in layers(c) if kind == FULL)
    return full * _token_bytes(c, cache_bytes)


def window_cache_bytes_per_sequence(c: Dict[str, Any], chunk: int,
                                    block_size: int,
                                    cache_bytes: int = 2) -> int:
    sliding = sum(1 for kind, _, _ in layers(c) if kind == SLIDING)
    ring = (-(-(int(c["sliding_window"]) + chunk) // block_size) + 1) * \
        block_size
    return sliding * ring * _token_bytes(c, cache_bytes)


# ---------------------------------------------------------------------------
# the family's kernels: operations and bytes of what they were given
# ---------------------------------------------------------------------------

def grouped_matmul_counts(c: Dict[str, Any], rows: float,
                          expert_calls: float, weight_bytes: int = 2
                          ) -> Dict[str, float]:
    """Both grouped matmuls of the routed experts (``moe_grouped_matmul*``:
    gate and up in one, then down) over ``rows`` (token, pick) pairs that
    fell on held experts, in ``expert_calls`` (layer, expert) calls that
    had at least one row. Operations: ``6 E I`` a row. Bytes: an expert's
    three matrices once a call, and a row's input, hidden and output
    vectors."""
    E, I = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    return {"flops": rows * 6.0 * E * I,
            "bytes": (expert_calls * 3.0 * E * I * weight_bytes +
                      rows * (2 * E + 2 * I) * weight_bytes)}


def _heads(c: Dict[str, Any], kind: str) -> int:
    return next(h for k, h, _ in layers(c) if k == kind)


def _attention_counts(c, kind, tokens_read, tokens_copied, cache_bytes):
    copied = tokens_read if tokens_copied is None else tokens_copied
    return {"flops": tokens_read * attention_flops_per_cache_token(
                c, _heads(c, kind)),
            "bytes": copied * _token_bytes(c, cache_bytes)}


def full_attention_counts(c: Dict[str, Any], tokens_read: float,
                          tokens_copied: float = None, cache_bytes: int = 2
                          ) -> Dict[str, float]:
    """The paged kernel of the full layers (``paged_attention_q1`` /
    ``_mq``) over ``tokens_read`` cache tokens (live tokens a query LANE
    attended, summed over lanes and layers): ``4 H D`` operations each.
    Bytes: a cache token is 4,096 B (keys and values of 8 heads of 128 in
    bf16) ONCE a row, whose query lanes share the pages the kernel
    copies: ``tokens_copied`` (whole pages a row's call copied, summed
    over rows and layers; without it every lane is taken to copy its own,
    which is right for a decode step alone). Queries and outputs are left
    out, so the share reads low rather than high."""
    return _attention_counts(c, FULL, tokens_read, tokens_copied,
                             cache_bytes)


def window_attention_counts(c: Dict[str, Any], tokens_read: float,
                            tokens_copied: float = None,
                            cache_bytes: int = 2) -> Dict[str, float]:
    """The same for the window-bounded form of the sliding layers
    (``paged_attention_window_q1`` / ``_mq``), whose lane attends at most
    ``sliding_window`` tokens."""
    return _attention_counts(c, SLIDING, tokens_read, tokens_copied,
                             cache_bytes)
