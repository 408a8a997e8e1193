"""Train the one fixed SVM that every benchmark configuration serves.

    PYTHONPATH=src python chipbench/svm/make_svm.py [--out chipbench/svm/svm.json]

Runs on the CPU (JAX_PLATFORMS=cpu is set here), once; the benchmark
only reads the weights file it writes, so set-up never trains.

Recipe, all from SEED:
  * windows: the paper's training split sizes (4,202 positive and 2,795
    negative 130x66 synthetic windows, `make_windows`), HOG descriptors
    of the paper's geometry (3,780 features);
  * schedule: the paper preset's `TRAIN` (Pegasos SGD, 4,000 steps,
    negative weight 6);
  * hard-negative mining (Dalal-Triggs bootstrapping), ROUNDS rounds:
    sweep the current head densely over person-free frames of the
    benchmark's own kind (`chipbench/clips.py`, the clips the cells
    serve, with no person pasted in) at both cell frame sizes and the
    cells' pyramids, crop every window scoring above MINE_THRESHOLD back
    to 130x66, and retrain with the crops as extra negatives.

The weights file holds the 3,780 weights and the bias as float32
values, the recipe, and per round the number of mined negatives.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))

SEED = 20220505
ROUNDS = 3
MINE_THRESHOLD = -0.5
#: person-free frames swept per round, per frame size
MINE_FRAMES = {"1080x1920": 3, "480x640": 12}


def _pyramid(h: int) -> tuple:
    """The cells' pyramid: steps of 0.8 from 1.0 while a 130-px window
    still fits the bucket-padded height."""
    ph = -(-h // 32) * 32
    out, s = [], 1.0
    while int(ph * s) >= 130:
        out.append(round(s, 10))
        s *= 0.8
    return tuple(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(HERE / "svm.json"))
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.clips import make_clip
    from repro.configs import hog_svm
    from repro.core.detector import DetectorConfig, FrameDetector
    from repro.core.hog import hog_descriptor
    from repro.core.svm import train_svm
    from repro.data.synth_pedestrian import PedestrianDataConfig, make_windows

    rng = np.random.default_rng(SEED)
    data = PedestrianDataConfig()
    x, y = make_windows(data.n_pos, data.n_neg, data, rng)
    feats = np.asarray(hog_descriptor(jnp.asarray(x), hog_svm.CONFIG))
    labels = np.asarray(y)
    svm, _ = train_svm(jnp.asarray(feats), jnp.asarray(labels), hog_svm.TRAIN)
    mined = []
    for r in range(ROUNDS):
        crops = []
        for size, n in MINE_FRAMES.items():
            h, w = (int(v) for v in size.split("x"))
            det = FrameDetector(svm, DetectorConfig(
                hog=hog_svm.CONFIG, scales=_pyramid(h),
                score_threshold=MINE_THRESHOLD))
            frames = make_clip(rng, h, w, n_frames=n, n_people=0)
            for frame in frames:
                for d in det.detect_raw(frame).to_list():
                    y0, x0, y1, x1 = (int(round(v)) for v in d["box"])
                    y0, x0, y1, x1 = max(0, y0), max(0, x0), min(h, y1), \
                        min(w, x1)
                    if y1 - y0 < 40 or x1 - x0 < 20:
                        continue
                    crops.append(np.asarray(jax.image.resize(
                        jnp.asarray(frame[y0:y1, x0:x1], jnp.float32),
                        (130, 66, 3), "linear")))
        mined.append(len(crops))
        print(f"round {r}: {len(crops)} hard negatives", flush=True)
        if not crops:
            break
        neg = np.clip(np.stack(crops), 0, 255).astype(np.uint8)
        feats = np.concatenate(
            [feats, np.asarray(hog_descriptor(jnp.asarray(neg),
                                              hog_svm.CONFIG))])
        labels = np.concatenate([labels, np.zeros(len(neg), labels.dtype)])
        svm, _ = train_svm(jnp.asarray(feats), jnp.asarray(labels),
                           hog_svm.TRAIN)

    out = {
        "seed": SEED,
        "train": dataclasses.asdict(hog_svm.TRAIN),
        "windows": {"positive": data.n_pos, "negative": data.n_neg},
        "mining": {"rounds": ROUNDS, "threshold": MINE_THRESHOLD,
                   "frames_per_round": MINE_FRAMES,
                   "negatives_per_round": mined},
        "b": float(np.float32(svm["b"])),
        "w": [float(v) for v in np.asarray(svm["w"], np.float32)],
    }
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {args.out}: |w| {np.linalg.norm(out['w'])}, b {out['b']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
