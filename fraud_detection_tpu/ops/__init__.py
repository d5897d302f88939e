"""Pallas TPU kernels for the framework's hot ops.

Histogram tree building reformulated as MXU matmuls (ops/histogram.py) —
the kernels BASELINE.json calls for — and device-side featurization
(ops/featurize_kernel.py): a byte-scan kernel that moves the serving
path's tokenize/murmur-hash/TF-count leg off the host entirely. XLA
reference paths live next to every kernel; every kernel takes
``interpret=`` so the CPU test mesh exercises them.
"""

from fraud_detection_tpu.ops.featurize_kernel import (
    FeaturizeSpec,
    build_stop_table,
    featurize_bytes,
    featurize_bytes_jit,
)
from fraud_detection_tpu.ops.histogram import (
    best_splits,
    histogram_reference,
    node_feature_bin_histogram,
    node_feature_bin_histogram_multi,
)

__all__ = [
    "FeaturizeSpec",
    "best_splits",
    "build_stop_table",
    "featurize_bytes",
    "featurize_bytes_jit",
    "histogram_reference",
    "node_feature_bin_histogram",
    "node_feature_bin_histogram_multi",
]
