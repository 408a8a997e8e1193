"""Paper Table I: accuracy of the detection system (160 pos / 134 neg).

Reproduces the full train->extract->classify chain on the synthetic
INRIA/MIT stand-in (see DESIGN.md §8.1) with the paper's split sizes and
reports the same three rows PER NUMERICS MODE: the fp32 chain (paper's
Matlab software role) and the fixed-point chain (the hardware datapath:
integer CORDIC, int16 histograms, int8 descriptors -- DESIGN.md §12).
Paper values: 83.75 % / 85.07 % / 84.35 %.

Each mode trains its own SVM on its own descriptors (the paper trains on
the datapath it deploys); the gate (`--check`) enforces total accuracy
>= 0.80 for every mode and |fixed - fp32| <= 1.5 points -- the fixed
chain must not cost detection quality.

The table is printed, and written to `--out PATH` when one is given (the
artifact the CI accuracy lane uploads).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs import hog_svm
from repro.core.hog import HOGConfig, PAPER_HOG, hog_descriptor
from repro.core.svm import SVMTrainConfig, accuracy_table, train_svm
from repro.data.synth_pedestrian import PedestrianDataConfig, make_dataset

PAPER = {"with_person_acc": 0.8375, "without_person_acc": 0.8507,
         "total_acc": 0.8435}

#: numerics modes Table I is reproduced for. fp32 = the software oracle
#: chain; fixed = the quantized datapath (the paper's actual hardware).
MODES: Dict[str, HOGConfig] = {
    "fp32": PAPER_HOG,
    "fixed": hog_svm.QUANT,
}

#: CI gate thresholds (--check): every mode's total accuracy, and the
#: fixed-vs-fp32 total-accuracy gap in accuracy points
MIN_TOTAL_ACC = 0.80
MAX_FIXED_GAP_PTS = 1.5


def _extract(x: np.ndarray, cfg: HOGConfig) -> np.ndarray:
    return np.asarray(hog_descriptor(jnp.asarray(x), cfg))


def run(fast: bool = False,
        data_cfg: Optional[PedestrianDataConfig] = None,
        modes: Sequence[str] = tuple(MODES),
        train_cfg: SVMTrainConfig = SVMTrainConfig(steps=4000,
                                                   neg_weight=3.0),
        ) -> Dict[str, float]:
    """Table I per numerics mode. Returns a FLAT metrics dict
    (mode-prefixed scalar keys).

    fast=True shrinks only the TRAIN split sizes of `data_cfg` via
    dataclasses.replace -- any other non-default dataset field (noise,
    contrast, seed, ...) the caller configured is preserved. (The old
    code rebuilt PedestrianDataConfig(n_pos=..., n_neg=...) from
    scratch, silently resetting every other field to its default.)
    """
    cfg = data_cfg if data_cfg is not None else PedestrianDataConfig()
    if fast:
        # 2400/1650 is the smallest split where BOTH numerics modes
        # clear the 0.80 gate with the seeded Pegasos run (800/550, the
        # old shrink, lands ~0.72 -- under the gate, not a regression)
        cfg = dataclasses.replace(cfg, n_pos=2400, n_neg=1650)

    x_tr, y_tr, x_te, y_te = make_dataset(cfg)
    y_trj, y_tej = jnp.asarray(y_tr), jnp.asarray(y_te)

    metrics: Dict[str, float] = {
        "fast": bool(fast), "n_train": int(len(y_tr)),
        "n_test": int(len(y_te)),
    }
    print("# Table I -- accuracy (ours vs paper), per numerics mode")
    for mode in modes:
        hog_cfg = MODES[mode]
        t0 = time.time()
        f_tr = _extract(x_tr, hog_cfg)
        f_te = _extract(x_te, hog_cfg)
        t_extract = time.time() - t0

        t0 = time.time()
        params, _ = train_svm(jnp.asarray(f_tr), y_trj, train_cfg)
        t_train = time.time() - t0

        acc = accuracy_table(params, jnp.asarray(f_te), y_tej)
        for key in ("with_person_acc", "without_person_acc", "total_acc"):
            metrics[f"{mode}_{key}"] = float(acc[key])
            print(f"table1/{mode}/{key},{acc[key]:.4f},"
                  f"paper={PAPER[key]:.4f}")
        metrics[f"{mode}_train_s"] = float(t_train)
        metrics[f"{mode}_extract_s"] = float(t_extract)
        metrics[f"{mode}_gap_vs_paper_pts"] = \
            (float(acc["total_acc"]) - PAPER["total_acc"]) * 100.0
        print(f"table1/{mode}/train_time_s,{t_train:.1f},paper=298.3")

    if "fp32" in modes and "fixed" in modes:
        gap = (metrics["fixed_total_acc"] - metrics["fp32_total_acc"]) * 100
        metrics["fixed_vs_fp32_gap_pts"] = float(gap)
        print(f"table1/fixed_vs_fp32_gap_pts,{gap:+.2f},gate<= "
              f"{MAX_FIXED_GAP_PTS}")

    return metrics


def check(metrics: Dict[str, float],
          modes: Sequence[str] = tuple(MODES)) -> int:
    """CI gate: 0 iff every mode's total accuracy clears MIN_TOTAL_ACC
    and the fixed-vs-fp32 gap is within MAX_FIXED_GAP_PTS points."""
    failures = []
    for mode in modes:
        total = metrics.get(f"{mode}_total_acc")
        if total is None or total < MIN_TOTAL_ACC:
            failures.append(f"{mode}_total_acc={total} < {MIN_TOTAL_ACC}")
    gap = metrics.get("fixed_vs_fp32_gap_pts")
    if gap is not None and abs(gap) > MAX_FIXED_GAP_PTS:
        failures.append(
            f"|fixed_vs_fp32_gap_pts|={abs(gap):.2f} > {MAX_FIXED_GAP_PTS}")
    for f in failures:
        print(f"accuracy-gate/FAIL,{f}")
    if not failures:
        print("accuracy-gate/ok,all thresholds cleared")
    return 1 if failures else 0


def format_table(metrics: Dict[str, float],
                 modes: Sequence[str] = tuple(MODES)) -> str:
    """The Table I artifact (plain text) the CI lane uploads."""
    rows = [("row", *modes, "paper")]
    for key, paper in (("with_person_acc", PAPER["with_person_acc"]),
                       ("without_person_acc", PAPER["without_person_acc"]),
                       ("total_acc", PAPER["total_acc"])):
        rows.append((key,
                     *(f"{metrics.get(f'{m}_{key}', float('nan')):.4f}"
                       for m in modes),
                     f"{paper:.4f}"))
    rows.append(("train_s",
                 *(f"{metrics.get(f'{m}_train_s', float('nan')):.1f}"
                   for m in modes), "298.3"))
    if "fixed_vs_fp32_gap_pts" in metrics:
        rows.append(("fixed_vs_fp32_gap_pts",
                     *([f"{metrics['fixed_vs_fp32_gap_pts']:+.2f}"]
                       + [""] * (len(modes) - 1)),
                     f"<={MAX_FIXED_GAP_PTS}"))
    widths = [max(len(str(r[i])) for r in rows) for i in range(len(rows[0]))]
    return "\n".join(
        "  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip()
        for r in rows) + "\n"


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="shrink the train split (800/550) for CI")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless every mode's total accuracy >= "
                         f"{MIN_TOTAL_ACC} and the fixed-vs-fp32 gap is "
                         f"within {MAX_FIXED_GAP_PTS} points")
    ap.add_argument("--out", type=str, default=None, metavar="PATH",
                    help="also write the Table I text artifact here")
    ap.add_argument("--modes", type=str, default=",".join(MODES),
                    help="comma-separated subset of: " + ",".join(MODES))
    a = ap.parse_args(argv)
    modes = tuple(m for m in a.modes.split(",") if m)
    unknown = [m for m in modes if m not in MODES]
    if unknown:
        ap.error(f"unknown modes {unknown}; available: {sorted(MODES)}")
    metrics = run(fast=a.fast, modes=modes)
    table = format_table(metrics, modes)
    print(table, end="")
    if a.out:
        import pathlib
        pathlib.Path(a.out).write_text(table)
        print(f"table1/artifact,{a.out},written")
    return check(metrics, modes) if a.check else 0


if __name__ == "__main__":
    raise SystemExit(main())
