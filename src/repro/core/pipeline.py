"""Batched detection pipeline -- the "co-processor" as a batched service op.

`classify_windows(params, windows)` is the TPU equivalent of the paper's
Fig. 6 datapath: grayscale -> HOG -> SVM -> {0, 1}, for a BATCH of windows
(the FPGA streams one window; the TPU streams a batch per grid step).

Execution paths (all numerically cross-validated in tests):
  * path="ref"     pure-jnp oracle (core/hog.py), mode per HOGConfig
  * path="kernel"  Pallas kernels (kernels/ops.py): gradient+bin, cell
                   histogram, block-norm, SVM matmul as separate kernels
  * path="fused"   single fused Pallas kernel per window batch (the §Perf
                   hillclimb artifact)
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.hog import HOGConfig, PAPER_HOG
from repro.core.svm import SVMParams, svm_score

Array = jax.Array


@partial(jax.jit, static_argnames=("cfg", "path"))
def extract_features(windows: Array, cfg: HOGConfig = PAPER_HOG,
                     path: str = "ref") -> Array:
    """(B, 130, 66, 3) uint8 -> (B, 3780) float32 descriptors.

    One stage chain (core/stages.py), three backends; windows smaller
    than the configured geometry raise ValueError at trace time.
    """
    from repro.core.stages import window_descriptor
    return window_descriptor(windows, cfg, backend=path)


@partial(jax.jit, static_argnames=("cfg", "path"))
def classify_windows(params: SVMParams, windows: Array,
                     cfg: HOGConfig = PAPER_HOG, path: str = "ref") -> Dict[str, Array]:
    """Full co-processor op: windows -> {score, human}. (Fig. 6 datapath.)"""
    feats = extract_features(windows, cfg, path)
    if path in ("kernel", "fused"):
        from repro.kernels import ops
        score = ops.svm_score_kernel(feats, params["w"], params["b"])
    elif cfg.feat_dtype == "bf16":
        # §Perf: bf16 descriptors AND weights on the wire, fp32 MXU
        # accumulation -- otherwise XLA promotes the descriptor back to
        # f32 before the dot and the down-cast is dead code
        score = jax.lax.dot_general(
            feats, params["w"].astype(jnp.bfloat16)[:, None],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[:, 0] + params["b"]
    else:
        score = svm_score(params, feats)
    return {"score": score, "human": (score > 0).astype(jnp.int32)}

