"""Everything the benchmark knows of one family of model: the decoder that
``paddle_tpu.models.pangu_ultra_moe`` computes (sandwich norms, multi-head
latent attention with a compressed cache, a leading dense layer and then
routed experts beside a shared one, of which this chip holds a share). A
configuration names this file with ``"model": "pangu_ultra_moe"``.

Three parts: the program's objects (its config and its seeded weights),
the counts of parameters, operations and cache bytes, and the operations
and bytes of ONE call of each kernel the family brought, from its shapes
and row counts: what a roofline share is worked out from. All counts come
from the configuration file's keys (or, for the readers, from the same
keys as the engine reports them in ``stats()["model"]``). A multiply-add
is two operations.

The configuration file counts what is HELD here: ``n_routed_experts`` is
the experts this chip holds (the router keeps the published width,
``published_counts.n_routed_experts``), ``vocab_size`` its rows of the
vocabulary.
"""

from __future__ import annotations

import functools
from typing import Any, Dict


# ---------------------------------------------------------------------------
# the program's objects
# ---------------------------------------------------------------------------

def router_width(config: Dict[str, Any]) -> int:
    """Experts the router scores: the published count where the file holds
    a share, the count held where it holds them all. (``stats()["model"]``
    says ``n_local_experts`` for the share.)"""
    return int(config.get("published_counts", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


def held_experts(config: Dict[str, Any]) -> int:
    return int(config.get("n_local_experts", config["n_routed_experts"]))


def program_config(config: Dict[str, Any], **program):
    import jax.numpy as jnp
    from paddle_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    for key in ("dtype", "param_dtype"):
        if key in program:
            program[key] = dtypes[program[key]]
    if int(config["num_key_value_heads"]) != int(
            config["num_attention_heads"]):
        raise ValueError("latent attention has one compressed vector for "
                         "every head: num_key_value_heads must equal "
                         "num_attention_heads")
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_shared_experts", "num_experts_per_tok",
            "max_position_embeddings")
    return PanguUltraMoEConfig(
        **{k: int(config[k]) for k in same},
        n_routed_experts=router_width(config),
        n_local_experts=int(config["n_routed_experts"]),
        expert_offset=int(config.get("expert_offset", 0)),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        **program)


def vocab_size(config: Dict[str, Any]) -> int:
    """How many token ids the traffic may draw from: the rows held."""
    return int(config["vocab_size"])


def param_shapes(cfg):
    import jax
    from paddle_tpu.models import pangu_ultra_moe as family
    return jax.eval_shape(functools.partial(family.init_params, cfg),
                          jax.random.key(0))


def make_weights(cfg, seed: int, shardings=None):
    """Seeded random weights on the device, in ONE jitted call, in the type
    they are stored in, laid out as the program lays them out: norms at
    one, every matrix normal with variance 1 / (rows it contracts over),
    the embedding at unit variance."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.pangu_ultra_moe import fan_in
    paths, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("ln"):
                leaves.append(jnp.ones(s.shape, s.dtype))
                continue
            rows = 1.0 if name == "embed" else float(fan_in(name, s.shape))
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

def _dims(c: Dict[str, Any]):
    return (int(c["hidden_size"]), int(c["num_attention_heads"]),
            int(c["q_lora_rank"]), int(c["kv_lora_rank"]),
            int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
            int(c["v_head_dim"]))


def attention_params(c: Dict[str, Any]) -> int:
    """The five matrices of one layer's latent attention."""
    E, H, Rq, R, dn, dr, dv = _dims(c)
    return (E * Rq + Rq * H * (dn + dr) + E * (R + dr) + R * H * (dn + dv)
            + H * dv * E)


def expert_params(c: Dict[str, Any]) -> int:
    """One expert (a routed one, or the shared one a shared expert)."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def _norm_params(c: Dict[str, Any]) -> int:
    return (4 * int(c["hidden_size"]) + int(c["q_lora_rank"]) +
            int(c["kv_lora_rank"]))


def _layers(c: Dict[str, Any]):
    dense = int(c.get("first_k_dense_replace", 0))
    return dense, int(c["num_hidden_layers"]) - dense


def layer_matmul_params(c: Dict[str, Any], kind: str) -> int:
    """Matrices one layer holds HERE (``kind`` ``"dense"`` or ``"moe"``)."""
    E = int(c["hidden_size"])
    if kind == "dense":
        return attention_params(c) + 3 * E * int(c["intermediate_size"])
    return (attention_params(c) + E * router_width(c) +
            (int(c["n_shared_experts"]) + held_experts(c)) *
            expert_params(c))


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters held here that a token can be multiplied with: the
    layers' matrices and the output head (the embedding is a gather)."""
    dense, moe = _layers(c)
    return (dense * layer_matmul_params(c, "dense") +
            moe * layer_matmul_params(c, "moe") +
            int(c["hidden_size"]) * int(c["vocab_size"]))


def total_params(c: Dict[str, Any]) -> int:
    """Every parameter stored here."""
    E, V = int(c["hidden_size"]), int(c["vocab_size"])
    return (matmul_params(c) + V * E + E +
            int(c["num_hidden_layers"]) * _norm_params(c))


def active_matmul_params(c: Dict[str, Any], head: bool = True) -> float:
    """Parameters ONE token is multiplied with on this chip: attention,
    router and shared expert of every layer, the dense layers' FFN, and of
    the routed experts its picks that fall here: ``num_experts_per_tok``
    times the share of the experts held (uniform routing, which seeded
    random weights give)."""
    E = int(c["hidden_size"])
    dense, moe = _layers(c)
    picks_here = (int(c["num_experts_per_tok"]) * held_experts(c) /
                  router_width(c))
    n = (dense * layer_matmul_params(c, "dense") +
         moe * (attention_params(c) + E * router_width(c) +
                (int(c["n_shared_experts"]) + picks_here) * expert_params(c)))
    return n + (E * int(c["vocab_size"]) if head else 0)


def attention_flops_per_cache_token(c: Dict[str, Any]) -> int:
    """Operations of the absorbed attention for ONE query token against
    ONE cache token of ONE layer: every head scores ``R + dr`` lanes and
    sums ``R`` value lanes."""
    _, H, _, R, _, dr, _ = _dims(c)
    return 2 * H * (2 * R + dr)


def serve_flops_per_token(c: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations one served token needs on this chip at a cache
    of ``context`` tokens (its own included): two a matmul parameter it
    meets, plus the absorbed attention over the cache in every layer.
    ``head=False`` for a prompt token, whose logits are not made."""
    return (2.0 * active_matmul_params(c, head) +
            int(c["num_hidden_layers"]) * context *
            attention_flops_per_cache_token(c))


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations one token of a ``seq``-token causal
    sequence would need in the expanded form (no cell trains this family;
    the contract asks the count of every family): 6 a matmul parameter it
    meets, plus per-head scores over ``dn + dr`` and values over ``dv``,
    the square halved by the mask, times 3."""
    _, H, _, _, dn, dr, dv = _dims(c)
    attention = 3 * (2 * seq * H * (dn + dr + dv)) / 2 * int(
        c["num_hidden_layers"])
    return 6.0 * active_matmul_params(c) + attention


def cache_bytes_per_token(c: Dict[str, Any], cache_bytes: int = 2) -> int:
    """What one token's cache HOLDS over all layers: the compressed vector
    and the rotary key. (The pool lays each vector out in whole 128-lane
    tiles: ``pool_bytes_per_token``.)"""
    _, _, _, R, _, dr, _ = _dims(c)
    return int(c["num_hidden_layers"]) * (R + dr) * cache_bytes


def pool_bytes_per_token(c: Dict[str, Any], cache_bytes: int = 2) -> int:
    _, _, _, R, _, dr, _ = _dims(c)
    return (int(c["num_hidden_layers"]) * (-(-(R + dr) // 128) * 128) *
            cache_bytes)


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
    three matrices once a call (the kernel reads an expert's weights once
    however many rows it has, up to 128), and a row's input, hidden and
    output vectors."""
    E, I = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    return {"flops": rows * 6.0 * E * I,
            "bytes": (expert_calls * 3.0 * E * I * weight_bytes +
                      rows * (2 * E + 2 * I) * weight_bytes)}


def latent_attention_counts(c: Dict[str, Any], tokens_read: float,
                            cache_bytes: int = 2) -> Dict[str, float]:
    """The latent paged-attention kernel (``paged_attention_latent``) over
    ``tokens_read`` cache tokens (live tokens a query lane, summed over
    lanes and layers): a token is ``R + dr`` values read once a lane, and
    ``attention_flops_per_cache_token`` operations. Queries and outputs
    (a lane's ``H x (R + dr)`` and ``H x R``) are left out: at a cache of
    a few hundred tokens they are a third of the traffic, so the share
    reads low rather than high."""
    _, _, _, R, _, dr, _ = _dims(c)
    return {"flops": tokens_read * attention_flops_per_cache_token(c),
            "bytes": tokens_read * (R + dr) * cache_bytes}
