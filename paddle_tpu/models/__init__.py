"""Flagship model zoo (the reference ships these via PaddleNLP/PaddleClas —
SURVEY §2.6 ecosystem row; here they are first-class so the framework is
benchmarkable end-to-end).

``llama`` is the flagship decoder family: a pure-functional, scan-over-stacked-
layers implementation designed for XLA (single trace regardless of depth,
pipeline-ready stacked params) plus sharding-spec builders for the hybrid mesh.
"""

from . import bert, llama  # noqa: F401
from .bert import (BertConfig, BertForPretraining,
                   BertForSequenceClassification, BertModel)  # noqa: F401
from .llama import (LlamaConfig, LlamaForCausalLM, init_params, forward,
                    loss_fn, param_specs)  # noqa: F401


def paged_family(model_config):
    """The module that holds the paged serving entry points of
    ``model_config``'s family: ``paged_prefill``,
    ``paged_decode_step``, ``paged_mixed_step`` (and ``paged_spec_step``
    where it has a verify step), ``init_paged_pool``,
    ``paged_pool_block_bytes``, and beside them ``PAGED_COUNTERS`` (names
    of the per-dispatch counters its entry points return third; empty for
    a family that counts nothing), ``validate_serving(cfg, serving_config)``
    (raises for what the family does not serve), ``describe(cfg)`` (the
    widths ``stats()["model"]`` shows, or None) and ``health(counters,
    cfg)`` (``health_snapshot()["family"]``, or None). Two more where a
    family has them: ``paged_cache_groups(cfg, block_size)`` (layers that
    share a block table, and the window groups' rings) and
    ``mixed_lane_budget(cfg, max_slots)`` (the query lanes one mixed step
    is kept to; without it a step carries every prompt in prefill). A
    config names its
    module in a ``paged_family`` attribute; one that has none
    (``LlamaConfig``) is served by ``models.generation``. This is how
    ``inference.serving`` reaches a family: through the config object it
    is given."""
    import importlib
    return importlib.import_module(
        getattr(model_config, "paged_family", None) or __name__ + ".generation")
