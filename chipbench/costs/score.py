"""SVM scoring's matmul on every pyramid level
(kernels/svm_matmul.py:score_matmul).

Scoring factors the 15x7x36 window sum through per-offset partial
products: one (block positions x 36) @ (36 x 105 window offsets) matmul
per level, then 105 shifted adds that collate the scores. This file
counts the matmul, the part the kernel does: 2 x 36 x 105 operations
per block position (a multiply and an add). Of bytes it counts only the
36 x 105 weights, read once: the compiler may keep a level's block grid
and the kernel's partial sums in the chip's on-chip memory (VMEM)
between operations, so their traffic to HBM is no lower bound on the
kernel's time. On a TPU v5e the kernel moved them faster than HBM
allows (PERF.md), so the least time is the operations' at the MXU's
peak. The shifted adds run outside the kernel and are neither counted
here nor timed by score_roofline.
"""
from chipbench import reference

BLOCK = 36                      # 2x2 cells x 9 bins
OFFSETS = 105                   # 15 x 7 block offsets of the window
BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def cost(h: int, w: int, det: dict, precision: dict) -> tuple:
    wgt = BYTES[precision["weights"]]
    ops = byts = 0
    for lv in reference.levels(h, w, det["scales"], det["shape_bucket"]):
        ops += 2 * BLOCK * OFFSETS * lv.bh * lv.bw
        byts += wgt * BLOCK * OFFSETS
    return int(ops), int(byts)
