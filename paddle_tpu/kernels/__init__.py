"""paddle_tpu.kernels — Pallas TPU kernels for the hot ops.

Parity target: the reference's fused kernel library
(``paddle/phi/kernels/fusion/``: flash_attn, fused_rms_norm, fused_rope; see
SURVEY.md §2.1 "Fused kernels"). Everything here operates on raw jax arrays; the
``nn.functional`` layer wraps them for Tensors and falls back to pure-jax
references where shapes/backends don't qualify. Kernels run in Pallas interpret
mode automatically off-TPU so the same code is testable on the CPU mesh;
the ONE backend/flag/interpret gate every kernel (and every caller choosing
between a kernel and its XLA fallback) resolves through is
:mod:`~paddle_tpu.kernels.dispatch` (``use_pallas``/``interpret``/``on_tpu``).
"""

from . import flash_attention as flash_attention_mod
from .dispatch import interpret, on_tpu, use_pallas
from .flash_attention import flash_attention, flash_attention_with_lse
from .grouped_matmul import grouped_matmul
from .paged_attention import paged_attention, paged_attention_latent
from .rms_norm import rms_norm
from .rope import apply_rope, rope_cos_sin

__all__ = ["flash_attention", "flash_attention_with_lse", "rms_norm",
           "apply_rope", "rope_cos_sin", "paged_attention",
           "paged_attention_latent", "grouped_matmul", "use_pallas",
           "interpret", "on_tpu"]
