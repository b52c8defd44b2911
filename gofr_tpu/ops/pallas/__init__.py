"""Pallas TPU kernels for the hot ops (see pallas_guide.md)."""

from gofr_tpu.ops.pallas.flash_attention import flash_attention
from gofr_tpu.ops.pallas.index_scores import index_scores
from gofr_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_decode_attention, ragged_paged_verify_attention)
from gofr_tpu.ops.pallas.select import (flash_tileable, index_tileable,
                                        ragged_tileable, scan_tileable,
                                        sparse_latent_tileable,
                                        step_tileable)
from gofr_tpu.ops.pallas.selective_scan import selective_scan
from gofr_tpu.ops.pallas.selective_step import selective_step
from gofr_tpu.ops.pallas.sparse_latent_attention import (
    sparse_latent_attention)

__all__ = ["flash_attention", "index_scores",
           "ragged_paged_decode_attention",
           "ragged_paged_verify_attention", "selective_scan",
           "selective_step", "sparse_latent_attention", "flash_tileable",
           "index_tileable", "ragged_tileable", "scan_tileable",
           "sparse_latent_tileable", "step_tileable"]
