"""Qwen2-MoE goes through the same adapter as Qwen2 (``qwen2.py``)."""

from benchmarks.chip.adapters.qwen2 import build, make_params

__all__ = ["build", "make_params"]
