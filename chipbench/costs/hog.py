"""HOG on every pyramid level (kernels/fused_hog.py:dense_fused_hog).

Per gradient pixel: 2 central differences, magnitude (2 multiplies, an
add, a square root), 8 bin-boundary tests (2 multiplies and a compare
each) and one histogram add: 31 operations. Per block: the sum of 36
squares (72), one reciprocal square root and 36 scalings: 109. Bytes:
the level's gray plane (trimmed to whole cells plus the 1-px border)
read once as float32, and its block grid written once in the
configuration's descriptor type.
"""
from chipbench import reference

OPS_PER_PIXEL = 31
OPS_PER_BLOCK = 109
BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def cost(h: int, w: int, det: dict, precision: dict) -> tuple:
    out = BYTES[precision["descriptors"]]
    ops = byts = 0
    for lv in reference.levels(h, w, det["scales"], det["shape_bucket"]):
        ops += OPS_PER_PIXEL * lv.gh * lv.gw + OPS_PER_BLOCK * lv.bh * lv.bw
        byts += 4 * (lv.gh + 2) * (lv.gw + 2) + out * 36 * lv.bh * lv.bw
    return int(ops), int(byts)
