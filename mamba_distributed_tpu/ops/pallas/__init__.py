"""Pallas TPU kernels for the hot ops.

The XLA formulations in ``ops/ssd.py``/``ops/scan.py`` are correct but pay
in HBM traffic: the SSD path materializes the (l x l) intra-chunk decay
matrix (O(b*t*h*l) bytes) per layer, and the selective-scan path remats
around a transient (b, l, d, n) tensor.  These kernels keep those
intermediates in VMEM instead — the SSD decay matrix is rebuilt per tile,
the selective-scan state lives in registers for the whole sequence — which
is where the MFU headroom lives (SURVEY.md §7 stage 5).  Decode-side,
``ragged_paged_decode_attention`` walks each slot's live pages of the
serving pool in blocks of B pages, every KV head of a page in one copy
(models/attention.py; docs/KERNELS.md).
"""

from mamba_distributed_tpu.ops.pallas.attention_kernels import (
    flash_sdpa_causal,
    ragged_paged_decode_attention,
)
from mamba_distributed_tpu.ops.pallas.scan_kernels import selective_scan_pallas
from mamba_distributed_tpu.ops.pallas.ssd_kernels import ssd_chunked_pallas

__all__ = [
    "flash_sdpa_causal",
    "ragged_paged_decode_attention",
    "selective_scan_pallas",
    "ssd_chunked_pallas",
]
