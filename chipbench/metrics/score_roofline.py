"""score_roofline: the scoring matmul's share of its roofline, in %:
the least time the traced frames' scoring matmul takes on the chip
(chipbench/costs/score.py, which counts the matmul alone) over the
summed device time of the matmul kernel's operations.

The Pallas matmul (kernels/svm_matmul.py:score_matmul) is one custom
call per pyramid level, named in the compiled program, and so in the
trace, after its jitted wrapper: `score_matmul.<n>`. The shifted adds
that collate its partial sums into window scores are XLA fusions
outside it, and are left out on both sides.
"""

PATTERN = r"^score_matmul(\.\d+)?$"


def read(obs):
    return obs.roofline("score", PATTERN)
