"""hog_roofline: the HOG kernel's share of its roofline, in %: the
least time the traced frames' HOG work takes on the chip
(chipbench/costs/hog.py) over the summed device time of the HOG
kernel's operations.

The dense fused Pallas kernel (kernels/fused_hog.py:dense_fused_hog) is
one custom call per pyramid level, named in the compiled program, and
so in the trace, after its jitted wrapper: `dense_fused_hog.<n>`.
"""

PATTERN = r"^dense_fused_hog(\.\d+)?$"


def read(obs):
    return obs.roofline("hog", PATTERN)
