"""Assigned architecture config: deepseek_v2_lite (see registry.py for the spec)."""
from .registry import deepseek_v2_lite as CONFIG  # noqa: F401
