"""The paper's own workload config: the HOG+SVM detection co-processor.

Geometry and numerics per Nguyen et al. (2022); PERF variant carries the
beyond-paper §Perf settings. Used by the api presets (api/config.py),
benchmarks/bench_accuracy.py and chipbench/svm/make_svm.py.
"""
import dataclasses

from repro.core.hog import HOGConfig
from repro.core.svm import SVMTrainConfig

# faithful: fp32 datapath, CORDIC magnitude/angle, NR rsqrt
FAITHFUL = HOGConfig(mode="cordic")

# TPU-native default: sector-compare binning, hardware rsqrt
CONFIG = HOGConfig(mode="sector")

# §Perf: bf16 descriptors + bf16 SVM weights (fp32 accumulation)
PERF = dataclasses.replace(CONFIG, feat_dtype="bf16")

# the paper's actual datapath: integer CORDIC gradients, int16 cell
# histograms, int8 block descriptors, int8 scoring matmul (DESIGN.md §12)
QUANT = HOGConfig(mode="cordic", numerics="fixed")

TRAIN = SVMTrainConfig(steps=4000, neg_weight=6.0)
