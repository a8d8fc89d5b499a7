"""Pallas TPU kernels for the hot ops.

The TPU-native replacement for the reference's hand-written CUDA kernels:
fused attention (operators/fused/multihead_matmul_op.cu and the
multihead_matmul_fuse_pass) and the sparse embedding update path
(SelectedRows, selected_rows.h:32 — segment_update.py deduped segment-sum,
one scatter per unique row), the recurrent state of power retention
(power_retention.py) and the sum back of an expert layer's rows
(moe_rows.py: one row DMA for each pair that holds a row).  Everything else
rides XLA fusion (SURVEY.md §7 design translation), convolution and batch
norm included.
"""

from .flash_attention import flash_attention  # noqa: F401
from .segment_update import apply_rows_update, dedup_segment_sum  # noqa: F401
