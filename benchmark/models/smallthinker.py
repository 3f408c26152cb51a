"""Everything the benchmark knows of the SmallThinker decoder as
``paddle_tpu.models.laguna`` computes it (the window/full family, with
this architecture's differences as fields of its configuration): window
layers with rotary embedding beside full layers with none (NoPE), one head
count and four kv heads in every layer, a router that reads the layer's
input before attention, a softmax over the top picks, ReGLU experts, no
shared expert and no dense layer. A configuration names this file with
``"model": "smallthinker"``.

Three parts: the program's objects (its config and its seeded weights),
the counts of parameters, operations and cache bytes, and the operations
and bytes of the kernels the family runs, from its shapes and its
counters: what a roofline share is worked out from. Every count reads the
published key names (``hidden_size``, ``moe_ffn_hidden_size``,
``moe_num_primary_experts``, ``moe_num_active_primary_experts``,
``sliding_window_layout``, ``sliding_window_size`` ...) of the
configuration file. The counts the readers call (``serve_flops_per_token``
and the kernels' counts) also take the program's own names, as the engine
reports them in ``stats()["model"]``: :func:`published` maps them. The
per-layer LIST keys stay as published, all their entries; entries
``[0, num_hidden_layers)`` are the layers held. A multiply-add is two
operations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

FULL, WINDOW = 0, 1          # a ``sliding_window_layout`` entry


# ---------------------------------------------------------------------------
# the program's objects
# ---------------------------------------------------------------------------

def published(c: Dict[str, Any]) -> Dict[str, Any]:
    """``c`` under the published key names: a configuration file as it is,
    or the window/full family's own names as ``laguna.describe`` gives
    them (one head count in every layer, the layers' kinds as 0/1 lists,
    rotary embedding in the window layers and, unless ``full_rope_type``
    is ``"none"``, in the full ones)."""
    if "moe_ffn_hidden_size" in c:
        return c
    window = [int(k == "sliding_attention") for k in c["layer_types"]]
    return {**c, "moe_ffn_hidden_size": c["moe_intermediate_size"],
            "moe_num_primary_experts": c["num_experts"],
            "moe_num_active_primary_experts": c["num_experts_per_tok"],
            "num_attention_heads": c["num_attention_heads_per_layer"][0],
            "sliding_window_size": c["sliding_window"],
            "sliding_window_layout": window,
            "rope_layout": [int(w or c["full_rope_type"] != "none")
                            for w in window]}


def kinds(config: Dict[str, Any]) -> List[int]:
    """``sliding_window_layout`` of each layer held: 1 a window layer."""
    return [int(k) for k in
            config["sliding_window_layout"][:int(config["num_hidden_layers"])]]


def program_config(config: Dict[str, Any], **program):
    import jax.numpy as jnp
    from paddle_tpu.models.laguna import FULL as F, SLIDING as S, LagunaConfig
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    for key in ("dtype", "param_dtype"):
        if key in program:
            program[key] = dtypes[program[key]]
    n = int(config["num_hidden_layers"])
    if [int(r) for r in config["rope_layout"][:n]] != kinds(config):
        raise ValueError("rotary embedding in the window layers and in no "
                         "other (rope_layout == sliding_window_layout)")
    if not (config["moe_primary_router_apply_softmax"] and
            config["norm_topk_prob"]) or config.get("rope_scaling"):
        raise ValueError("the router is a softmax renormalised over its "
                         "picks, and the rotary embedding is unscaled")
    E = int(config["moe_num_primary_experts"])
    return LagunaConfig(
        family="smallthinker",
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=0,                      # no dense FFN
        moe_intermediate_size=int(config["moe_ffn_hidden_size"]),
        shared_expert_intermediate_size=0,        # no shared expert
        num_hidden_layers=n,
        num_key_value_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        layer_types=tuple(S if k == WINDOW else F for k in kinds(config)),
        num_attention_heads_per_layer=(int(config["num_attention_heads"]),)
        * n,
        sliding_window=int(config["sliding_window_size"]),
        num_experts=E, n_local_experts=E, expert_offset=0,
        num_experts_per_tok=int(config["moe_num_active_primary_experts"]),
        moe_routed_scaling_factor=1.0,
        rms_norm_eps=float(config["rms_norm_eps"]),
        full_rope_type="none",
        sliding_rope_theta=float(config["rope_theta"]),
        sliding_rotary_factor=1.0,
        window_chunk=int(config.get("engine", {}).get("prefill_chunk", 128)),
        first_layer_dense=False, head_gate=False,
        router_before_attention=True, router_score="softmax",
        expert_activation="relu",
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
    they are stored in, laid out as the program lays them out: norms drawn
    about one (so that a norm put where none is, or left out, shows),
    every matrix normal with variance 1 / (rows it contracts over), the
    embedding at unit variance."""
    import jax
    import jax.numpy as jnp
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name.startswith("ln"):
                w = 1.0 + 0.2 * jax.random.normal(k, s.shape, jnp.float32)
            else:
                rows = 1.0 if name == "embed" else float(s.shape[-2])
                w = jax.random.normal(k, s.shape, jnp.float32) * rows ** -0.5
            leaves.append(w.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# counts, from the configuration file's keys
# ---------------------------------------------------------------------------

def attention_params(c: Dict[str, Any]) -> int:
    """Queries, keys, values and the output of one layer."""
    E, D = int(c["hidden_size"]), int(c["head_dim"])
    H, Hk = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return 2 * E * H * D + 2 * E * Hk * D


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_ffn_hidden_size"])


def layer_matmul_params(c: Dict[str, Any], picks=None) -> float:
    """Matrices of one layer; with ``picks`` the experts a TOKEN meets
    instead of all those held."""
    experts = int(c["moe_num_primary_experts"]) if picks is None else picks
    return (attention_params(c) +
            int(c["hidden_size"]) * int(c["moe_num_primary_experts"]) +
            experts * expert_params(c))


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters held here that a token can be multiplied with: the
    layers' matrices and the output head (the embedding is a gather)."""
    return (int(c["num_hidden_layers"]) * layer_matmul_params(c) +
            int(c["hidden_size"]) * int(c["vocab_size"]))


def total_params(c: Dict[str, Any]) -> int:
    """Every parameter stored here."""
    E, V = int(c["hidden_size"]), int(c["vocab_size"])
    return matmul_params(c) + V * E + E + int(c["num_hidden_layers"]) * 2 * E


def active_matmul_params(c: Dict[str, Any], head: bool = True) -> float:
    """Parameters ONE token is multiplied with: attention and the router
    of every layer, its ``moe_num_active_primary_experts`` experts, and
    the output head."""
    n = int(c["num_hidden_layers"]) * layer_matmul_params(
        c, int(c["moe_num_active_primary_experts"]))
    return n + (int(c["hidden_size"]) * int(c["vocab_size"]) if head else 0)


def attention_flops_per_cache_token(c: Dict[str, Any]) -> int:
    """Operations of ONE query token against ONE cache token of ONE layer:
    a score and a weighted value a head."""
    return 4 * int(c["num_attention_heads"]) * int(c["head_dim"])


def serve_flops_per_token(c: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations one served token needs at a cache of
    ``context`` tokens (its own included): two a matmul parameter it
    meets, plus attention over ``context`` tokens in a full layer and over
    ``min(context, sliding_window_size)`` in a window layer. ``head=False``
    for a prompt token, whose logits are not made. ``mfu_pct.sat`` passes
    0 here (it takes the context from a counter this family does not
    report) and so reads the matmul part alone."""
    c = published(c)
    window = int(c["sliding_window_size"])
    attention = sum(attention_flops_per_cache_token(c) *
                    (min(context, window) if k == WINDOW else context)
                    for k in kinds(c))
    return 2.0 * active_matmul_params(c, head) + attention


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations one token of a ``seq``-token causal
    sequence would need (no cell trains this family; the contract asks the
    count of every family): 6 a matmul parameter it meets, plus the
    attention at the mean context, times 3."""
    window = int(c["sliding_window_size"])
    attention = sum(attention_flops_per_cache_token(c) *
                    (min(seq / 2, window) if k == WINDOW else seq / 2)
                    for k in kinds(c))
    return 6.0 * active_matmul_params(c) + 3.0 * attention


def _token_bytes(c: Dict[str, Any], cache_bytes: int) -> int:
    """Keys and values of one token in one layer."""
    return (2 * int(c["num_key_value_heads"]) * int(c["head_dim"]) *
            cache_bytes)


def cache_bytes_per_token(c: Dict[str, Any], cache_bytes: int = 2) -> int:
    """What one more token ADDS to a sequence's cache: its keys and values
    in the FULL layers (2 x 2,048 B at this cut). The window layers hold a
    bounded ring a SEQUENCE: ``window_cache_bytes_per_sequence``."""
    return kinds(c).count(FULL) * _token_bytes(c, cache_bytes)


def window_cache_bytes_per_sequence(c: Dict[str, Any], chunk: int,
                                    block_size: int,
                                    cache_bytes: int = 2) -> int:
    """At most ``sliding_window_size + chunk`` tokens, rounded up to blocks
    plus one, in each window layer (6 x 2,048 B x 4,240 = 52.1 MB here)."""
    ring = (-(-(int(c["sliding_window_size"]) + chunk) // block_size) + 1) \
        * block_size
    return kinds(c).count(WINDOW) * ring * _token_bytes(c, cache_bytes)


# ---------------------------------------------------------------------------
# the family's kernels: operations and bytes of what they were given
# ---------------------------------------------------------------------------

def grouped_matmul_counts(c: Dict[str, Any], rows: float,
                          expert_calls: float, weight_bytes: int = 2
                          ) -> Dict[str, float]:
    """Both grouped matmuls of the experts (``moe_grouped_matmul*``: gate
    and up with the ReGLU in one, then down) over ``rows`` (token, pick)
    pairs in ``expert_calls`` (layer, expert) calls that had at least one
    row. Operations: ``6 E I`` a row. Bytes: an expert's three matrices
    once a call, and a row's input, hidden and output vectors."""
    c = published(c)
    E, I = int(c["hidden_size"]), int(c["moe_ffn_hidden_size"])
    return {"flops": rows * 6.0 * E * I,
            "bytes": (expert_calls * 3.0 * E * I * weight_bytes +
                      rows * (2 * E + 2 * I) * weight_bytes)}


def _attention_counts(c, tokens_read, tokens_copied, cache_bytes):
    c = published(c)
    copied = tokens_read if tokens_copied is None else tokens_copied
    return {"flops": tokens_read * attention_flops_per_cache_token(c),
            "bytes": copied * _token_bytes(c, cache_bytes)}


def full_attention_counts(c: Dict[str, Any], tokens_read: float,
                          tokens_copied: float = None, cache_bytes: int = 2
                          ) -> Dict[str, float]:
    """The paged kernel of the full layers (``paged_attention_q1`` /
    ``_mq``) over ``tokens_read`` cache tokens (live tokens a query LANE
    attended, summed over lanes and layers): ``4 H D`` operations each,
    and no rotary operation (NoPE). Bytes: a cache token is 2,048 B (keys
    and values of 4 heads of 128 in bf16) ONCE a row, whose query lanes
    share the pages the kernel copies: ``tokens_copied`` (whole pages a
    row's call copied, summed over rows and layers; without it every lane
    is taken to copy its own). Queries and outputs are left out, so the
    share reads low rather than high."""
    return _attention_counts(c, tokens_read, tokens_copied, cache_bytes)


def window_attention_counts(c: Dict[str, Any], tokens_read: float,
                            tokens_copied: float = None,
                            cache_bytes: int = 2) -> Dict[str, float]:
    """The same for the window-bounded form of the window layers
    (``paged_attention_window_q1`` / ``_mq``), whose lane attends at most
    ``sliding_window_size`` tokens. The rotary embedding is applied to
    queries and keys before the kernel and is not counted here."""
    return _attention_counts(c, tokens_read, tokens_copied, cache_bytes)
