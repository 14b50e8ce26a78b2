"""Plain float32 reference of Qwen2-MoE (``model_type`` qwen2_moe): the
Qwen2 layer with a routed and a shared expert in place of the MLP, as
``qwen2.py`` sets out."""

from benchmarks.chip.references.qwen2 import logits_at, weight_specs

__all__ = ["logits_at", "weight_specs"]
