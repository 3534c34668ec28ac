"""repro.core — the paper's contribution: VQ-AMM / LUT-based GEMM + LUTBoost."""
from .codebook import CodebookSpec, init_centroids, kmeans, kmeans_codebook
from .kv_codebook import (CODEBOOK_KEY, KVCodebook, codebook_from_tree,
                          kv_decode, kv_decode_stacked, kv_encode,
                          kv_encode_stacked)
from .lut import (DENSE, QuantConfig, build_lut, lut_linear_apply,
                  lut_linear_init, precompute_layer, quantize_lut_int8,
                  strip_for_inference)
from .lutboost import (LutBoostSchedule, apply_mask, capture_activations,
                       centroid_only_mask, convert, kmeans_init_from_capture,
                       precompute_model, stage_mask, strip_model)
from .similarity import (ALPHA_SIM, Metric, assign, assign_subspaces,
                         pairwise_distance, pairwise_distance_subspaces,
                         soft_assignment, ste_quantize,
                         ste_quantize_subspaces)
