"""``repro.dist`` — multi-device distributed execution.

The paper's core result is that per-operation overhead, not kernel
quality, dominates batch-1 inference; the scaling answer is fewer, larger
scheduled units amortized across devices and microbatches.  This package
provides the three mechanisms the roadmap names:

* :mod:`repro.dist.pipeline`    — GPipe-style microbatched pipeline
  parallelism over a ``("stage",)`` mesh axis (``shard_map`` + ``ppermute``
  rotation, fill/drain schedule, bubble-fraction accounting).
* :mod:`repro.dist.compression` — per-row-scaled int8 compressed
  all-reduce with error-feedback residuals, plus the pure
  quantize/dequantize kernels the trainer hook reuses.
* :mod:`repro.dist.elastic`     — checkpoint restore across mesh shapes
  (the "pod loss" re-scale path), on top of ``train/checkpoint.py`` and
  ``sharding/rules.py``.

The serving integration is ``repro.serving.backends.dist`` (registry key
``"dist"``), which drives prefill/decode through the pipeline schedule.
"""
from __future__ import annotations

from repro.dist.compression import (CompressionConfig, compress_gradients,
                                    compressed_psum_mean, dequantize_int8,
                                    quantize_int8, uncompressed_psum_mean)
from repro.dist.elastic import restore_on_mesh, state_shardings_for
from repro.dist.pipeline import (PipelineStats, bubble_fraction,
                                 pipeline_apply, pipeline_stats)

__all__ = [
    "PipelineStats", "bubble_fraction", "pipeline_apply", "pipeline_stats",
    "CompressionConfig", "compress_gradients", "compressed_psum_mean",
    "dequantize_int8", "quantize_int8", "uncompressed_psum_mean",
    "restore_on_mesh", "state_shardings_for",
]
