"""Operations, bytes and peaks: the arithmetic behind every utilisation.

All counts come from shapes. A multiply-add is two operations. Recomputed
operations (``remat``) are never counted: utilisation is useful work over
peak.
"""

from __future__ import annotations

from typing import Any, Dict

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
# A device that is not here is an error, never a default: a utilisation
# against the wrong chip is a wrong number.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks on record for device_kind {device_kind!r}; "
            f"add it to benchmark/flops.py PEAKS with its source") from None


def _dims(model: Dict[str, Any]):
    E = int(model["hidden_size"])
    H = int(model["num_attention_heads"])
    Hk = int(model.get("num_key_value_heads") or H)
    D = int(model.get("head_dim") or E // H)
    return (E, H, Hk, D, int(model["intermediate_size"]),
            int(model["vocab_size"]), int(model["num_hidden_layers"]))


def layer_params(model: Dict[str, Any]) -> int:
    """Parameters of one decoder layer: q, k, v, o, the gated FFN's three
    matrices and the two norm vectors."""
    E, H, Hk, D, I, _, _ = _dims(model)
    return E * H * D + 2 * E * Hk * D + H * D * E + 3 * E * I + 2 * E


def layer_matmul_params(model: Dict[str, Any]) -> int:
    E, *_ = _dims(model)
    return layer_params(model) - 2 * E


def total_params(model: Dict[str, Any]) -> int:
    """Every stored parameter: embedding table, layers, final norm and the
    untied output head."""
    E, _, _, _, _, V, L = _dims(model)
    head = 0 if model.get("tie_word_embeddings") else E * V
    return V * E + L * layer_params(model) + E + head


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with: the layers' matrices and the
    output head. The embedding table is a gather, not a matmul, and is left
    out (``bench._train_flops_per_step`` counts it; that is its fault)."""
    E, _, _, _, _, V, L = _dims(model)
    return L * layer_matmul_params(model) + E * V


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations one token of a ``seq``-token causal
    sequence needs: 6 per matmul parameter (2 forward, 4 backward) plus
    attention's two matmuls (QK^T and PV: 4*S*H*D a token forward over the
    full square, halved by the causal mask, times 3 for the backward
    pass)."""
    _, H, _, D, _, _, L = _dims(model)
    attention = 3 * (4 * seq * H * D) / 2 * L
    return 6.0 * matmul_params(model) + attention


def kv_bytes_per_token(model: Dict[str, Any], cache_bytes: int = 2) -> int:
    _, _, Hk, D, _, _, L = _dims(model)
    return L * 2 * Hk * D * cache_bytes
