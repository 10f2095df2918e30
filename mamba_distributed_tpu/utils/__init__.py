"""Shared utilities: FLOPs accounting, metrics logging."""

from mamba_distributed_tpu.utils.flops import flops_per_token, peak_flops_per_chip
from mamba_distributed_tpu.utils.metrics import MetricsLogger

__all__ = ["flops_per_token", "peak_flops_per_chip", "MetricsLogger"]
