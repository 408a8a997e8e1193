"""Pyramid resize: each downscaled level from the bucket-padded gray.

Counted as the algorithm needs it, whatever implements it: two passes of
the separable triangle filter, one multiply and one add per tap per
output value (rows pass over (level rows, padded columns), columns pass
over (level rows, level columns)); the padded gray read once and the
level written once, float32.
"""
from chipbench import reference


def cost(h: int, w: int, det: dict, precision: dict) -> tuple:
    ph, pw = reference.bucket(h, w, det["shape_bucket"])
    ops = byts = 0
    for lv in reference.levels(h, w, det["scales"], det["shape_bucket"]):
        if (lv.sh, lv.sw) == (ph, pw):
            continue
        taps_r = (reference.resize_taps(ph, lv.sh)[1] > 0).sum()
        taps_c = (reference.resize_taps(pw, lv.sw)[1] > 0).sum()
        ops += 2 * (taps_r * pw + taps_c * lv.sh)
        byts += 4 * (ph * pw + lv.sh * lv.sw)
    return int(ops), int(byts)
