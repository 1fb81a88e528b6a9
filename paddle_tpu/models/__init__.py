"""Flagship model zoo (functional, shard_map-ready).

The Layer-based zoo lives in paddle_tpu.vision.models; this package holds the
pure-functional flagship models used by the hybrid-parallel engine, the
serving engine, the graft entry point and the benchmark.
"""
from . import llama  # noqa: F401
from .llama import LlamaConfig  # noqa: F401
