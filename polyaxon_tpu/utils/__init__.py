"""Shared utilities: which devices a process runs on (jax_platform) and TPU
hardware metadata (tpu_info)."""

from .jax_platform import apply_platform, apply_platform_env  # noqa: F401
from .tpu_info import peak_bf16_flops  # noqa: F401
