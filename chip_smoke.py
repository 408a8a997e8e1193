"""Bring-up smoke: the detector's main path on a TPU, end to end.

Drives the paper's full-width model (hog_svm.CONFIG: 130x66 window, 8x8
cells, 2x2 blocks, 9 bins, 3780 features) densely over real frame sizes
through the user entry points (`repro.api.DetectionSession` and its
`serve()` DetectionService), with an SVM trained from `--seed` on the
synthetic pedestrian set at the `launch.detect --fast` sizes.

    python chip_smoke.py [--seed 0]            # one chip
    python chip_smoke.py --chips 4             # the four-chip mesh path

One chip, four phases over a few 1920x1080 and 640x480 scenes:
  (a) `perf`  -- dense fused Pallas HOG, bf16 descriptors, MXU scoring;
  (b) `quant` -- int8 CORDIC chain and the int8 scoring kernel;
  (c) the `ref` backend with each preset's numerics, as the comparison.
Each phase serves the frames twice: all frames are queued before the
service starts, so each size bucket runs as one multi-frame batch. The
first pass compiles; the second is the steady reading. Each phase prints
frames answered, frames with an error, batched-program fallbacks,
agreement with (c), whether the compiled frame program holds a Pallas
TPU kernel (`tpu_custom_call`; checked on the smallest frame size, for
the Pallas phases), and its timings. The timings are a
smoke reading, not a benchmark.

`--chips 4` runs only the mesh path: the `uhd` preset tiled over four
chips on 3840x2160 scenes against the same scenes untiled on one chip,
and the `sharded` preset's `detect_batch` of eight 1080p scenes over the
four-chip data mesh against one device. It checks that the outputs span
all four devices and prints each device's peak memory.

The script refuses to run (nonzero exit, no result line) unless JAX's
first device is a TPU; it has no CPU mode. Any error, fallback, missing
kernel or failed agreement exits nonzero without the result line. The
last line of a passing run is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import platform  # noqa: E402,F401  (env knobs before jax init)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import DetectionSession, presets  # noqa: E402
from repro.core.detector import autotune_report  # noqa: E402
from repro.data.synth_pedestrian import make_scene  # noqa: E402

FRAME_SIZES = ((1080, 1920), (480, 640))
FRAMES_PER_SIZE = 3
UHD = (2160, 3840)
SHARDED_BATCH = 8
# the box-agreement criterion of tests/test_stages_detector.py
# (test_perf_preset_matches_paper_preset_boxes): every detection clearing
# the threshold by MARGIN has a box twin within 1 px whose score is
# within TOL, in both directions
MARGIN = TOL = 0.05
#: per-future wait; a first pass compiles every program it needs
TIMEOUT_S = 900.0


def train_svm(seed: int):
    """SVM params trained from `seed` at the `launch.detect --fast`
    sizes (500 positive, 350 negative synthetic windows)."""
    return DetectionSession.train(presets("paper"), n_pos=500, n_neg=350,
                                  seed=seed).svm


def make_frames(seed: int, sizes, per_size: int):
    rng = np.random.default_rng(seed + 1)
    return [make_scene(rng, h, w, n_people=2)[0]
            for h, w in sizes for _ in range(per_size)]


def boxes_agree(got, want, threshold: float, margin: float = MARGIN,
                tol: float = TOL):
    """Problems (empty when none) between two per-frame detection lists
    under the box-agreement criterion, in both directions."""
    problems = []
    for i, (a, b) in enumerate(zip(got, want)):
        for src, dst, name in ((a, b, "got->want"), (b, a, "want->got")):
            for d in src:
                if d["score"] < threshold + margin:
                    continue
                twins = [e for e in dst
                         if np.allclose(e["box"], d["box"], atol=1.0)]
                if not twins or min(abs(e["score"] - d["score"])
                                    for e in twins) >= tol:
                    problems.append(f"frame {i} {name}: {d}")
    return problems


def serve_pass(session, frames, timeout: float = TIMEOUT_S):
    """One pass through `session.serve()`. Every frame is queued before
    the worker starts, so the microbatcher groups each size bucket into
    one batch. Returns (results, wall seconds, service stats)."""
    svc = session.serve()
    futs = [svc.submit_frame(f) for f in frames]
    t0 = time.perf_counter()
    svc.start()
    try:
        results = [f.get(timeout=timeout) for f in futs]
    finally:
        svc.stop()
    return results, time.perf_counter() - t0, svc.stats


def batch_error(session, frames) -> str:
    """Why the service fell back to frame-by-frame: the first error the
    batched program raises on one size's frames run directly."""
    for hw in sorted({np.shape(f)[:2] for f in frames}):
        same = [f for f in frames if np.shape(f)[:2] == hw]
        try:
            session.detect_batch(np.stack(same)).block_until_ready()
        except Exception as e:
            return f"{hw}: {type(e).__name__}: {e}"
    return "not reproduced by detect_batch"


def output_devices(result) -> set:
    """Ids of the devices holding a Detections result's device arrays."""
    return {d.id for leaf in jax.tree_util.tree_leaves(result)
            if hasattr(leaf, "sharding") for d in leaf.sharding.device_set}


def frame_program_text(session, h: int, w: int) -> str:
    """Compiled text of the frame program that serves (h, w) frames."""
    prog, ph, pw = session.detector.program_for(h, w)
    f32 = jnp.float32
    return prog.fn.lower(jax.ShapeDtypeStruct((ph, pw), f32),
                         session.svm["w"], session.svm["b"],
                         jax.ShapeDtypeStruct((2,), f32)).compile().as_text()


def run_phase(name: str, config, svm, frames, reference=None,
              timeout: float = TIMEOUT_S) -> dict:
    """Serve `frames` twice through a session of `config`; print one
    report line and return it with the pass-1 detections. A Pallas phase
    (backend != "ref") must hold a `tpu_custom_call`, and, when a
    `reference` phase is given, agree with its detections."""
    session = DetectionSession(svm, config)
    first, wall1, stats1 = serve_pass(session, frames, timeout)
    second, wall2, stats2 = serve_pass(session, frames, timeout)
    dets = [r["detections"] for r in first]
    n = len(frames)
    errors = [r["error"] for r in first + second if "error" in r]
    fallbacks = stats1["batch_fallbacks"] + stats2["batch_fallbacks"]
    pallas = config.detector.backend != "ref"
    # the smallest frame's program: the same kernels, the cheapest compile
    h, w = min(np.shape(f)[:2] for f in frames)
    custom = "tpu_custom_call" in frame_program_text(session, h, w) \
        if pallas else None
    rep = {
        "phase": name, "preset": config.name,
        "backend": config.detector.backend,
        "numerics": config.hog.numerics,
        "answered": 2 * n - len(errors), "errors": len(errors),
        "batch_fallbacks": fallbacks,
        "batches": stats1["frame_batches"] + stats2["frame_batches"],
        "boxes": sum(len(d) for d in dets),
        "tpu_custom_call": custom,
        "first_pass_s": wall1,
        "steady_ms_per_frame": wall2 * 1e3 / n,
    }
    problems = []
    if errors:
        problems.append(f"errors: {errors[:3]}")
    if fallbacks:
        problems.append(f"{fallbacks} batch fallbacks: "
                        f"{batch_error(session, frames)}")
    if rep["batches"] >= 2 * n:
        problems.append("no multi-frame batch ran")
    if pallas and not custom:
        problems.append("compiled frame program has no tpu_custom_call")
    if reference is not None:
        if not reference["boxes"]:
            problems.append("reference found no box: agreement is vacuous")
        disagree = boxes_agree(dets, reference["dets"],
                               config.detector.score_threshold)
        rep["agrees_with"] = reference["phase"]
        rep["agreement"] = not disagree
        problems += disagree[:5]
    rep["ok"] = not problems
    print(f"phase {name}: " + json.dumps(rep), flush=True)
    for p in problems:
        print(f"  FAIL {p}", flush=True)
    rep["dets"] = dets
    return rep


def one_chip_phases(svm, frames, timeout: float = TIMEOUT_S,
                    configure=None) -> bool:
    """Phases (a)/(b) against (c): for each preset, its `ref`-backend
    twin first, then the Pallas preset compared with it. `configure`
    maps a PipelineConfig to the one actually run (tests shrink it)."""
    ok = True
    for tag, name in (("a", "perf"), ("b", "quant")):
        cfg = presets(name)
        if configure is not None:
            cfg = configure(cfg)
        ref_cfg = cfg.replace(detector=dataclasses.replace(cfg.detector,
                                                           backend="ref"))
        ref = run_phase(f"c:{name}-ref", ref_cfg, svm, frames,
                        timeout=timeout)
        got = run_phase(f"{tag}:{name}", cfg, svm, frames, reference=ref,
                        timeout=timeout)
        ok = ok and ref["ok"] and got["ok"]
    return ok


# ------------------------------------------------------------- four chips

def _peak_bytes() -> dict:
    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out


def _identical(a, b) -> bool:
    return len(a) == len(b) and all(
        len(x) == len(y) and all(
            np.array_equal(u["box"], v["box"]) and u["score"] == v["score"]
            for u, v in zip(x, y)) for x, y in zip(a, b))


def _mesh_report(name, got, want, devices, n_dev, threshold) -> bool:
    disagree = boxes_agree(got, want, threshold)
    boxes = sum(len(d) for d in want)
    rep = {"phase": name, "boxes": boxes, "agreement": not disagree,
           "byte_identical": _identical(got, want),
           "output_devices": sorted(devices),
           "peak_bytes_in_use": _peak_bytes()}
    ok = not disagree and boxes > 0 and len(devices) == n_dev
    rep["ok"] = ok
    print(f"phase {name}: " + json.dumps(rep), flush=True)
    for p in disagree[:5]:
        print(f"  FAIL {p}", flush=True)
    if not boxes:
        print("  FAIL the single-device run found no box", flush=True)
    if len(devices) != n_dev:
        print(f"  FAIL outputs span {len(devices)} of {n_dev} devices",
              flush=True)
    return ok


def mesh_phases(svm, seed: int, n_dev: int, uhd=UHD, n_uhd: int = 2,
                batch_hw=FRAME_SIZES[0], batch: int = SHARDED_BATCH,
                configure=None) -> bool:
    """The `uhd` tiled path and the `sharded` data path over `n_dev`
    devices, each against its one-device twin."""
    def cfg_of(name, **det):
        cfg = presets(name)
        if configure is not None:
            cfg = configure(cfg)
        return cfg.replace(detector=dataclasses.replace(cfg.detector, **det))

    rng_seed = seed + 2
    uhd_frames = make_frames(rng_seed, (uhd,), n_uhd)
    tiled = DetectionSession(svm, cfg_of("uhd"))
    if tiled.detector.frame_devices != n_dev:
        print(f"  FAIL uhd preset tiles over {tiled.detector.frame_devices}"
              f" devices, not {n_dev}", flush=True)
        return False
    untiled = DetectionSession(svm, cfg_of("uhd", frame_parallel=1))
    got, want, devices = [], [], set()
    t0 = time.perf_counter()
    for f in uhd_frames:
        r = tiled.detect(f)
        devices |= output_devices(r)
        got.append(r.to_list())
    t_tiled = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = [untiled.detect(f).to_list() for f in uhd_frames]
    t_untiled = time.perf_counter() - t0
    print(f"uhd: {n_uhd} frames {uhd[1]}x{uhd[0]}, tiled pass "
          f"{t_tiled} s, untiled pass {t_untiled} s (compile included; "
          f"a smoke reading, not a benchmark)", flush=True)
    ok = _mesh_report("uhd-tiled-vs-untiled", got, want, devices, n_dev,
                      tiled.config.detector.score_threshold)

    frames = np.stack(make_frames(rng_seed + 1, (batch_hw,), batch))
    sharded = DetectionSession(svm, cfg_of("sharded"))
    if sharded.data_devices != n_dev:
        print(f"  FAIL sharded preset spans {sharded.data_devices} "
              f"devices, not {n_dev}", flush=True)
        return False
    single = DetectionSession(svm, cfg_of("sharded", data_parallel=1))
    r = sharded.detect_batch(frames)
    devices = output_devices(r)
    got = r.to_list()
    want = single.detect_batch(frames).to_list()
    ok = _mesh_report("sharded-vs-single", got, want, devices, n_dev,
                      sharded.config.detector.score_threshold) and ok
    # both presets autotune scan-vs-vmap by timing: the schedule each
    # side ran, since byte identity is pinned per schedule
    print("batch schedules: " + json.dumps(
        {k: {"chunk": v["chunk"], "probe_ms": v.get("probe_ms")}
         for k, v in autotune_report().items()}), flush=True)
    return ok


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the SVM training set and the scenes")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh path")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              f"TPU; this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)

    t0 = time.perf_counter()
    svm = train_svm(args.seed)
    print(f"svm: trained from seed {args.seed} in "
          f"{time.perf_counter() - t0} s", flush=True)
    if args.chips == 4:
        ok = mesh_phases(svm, args.seed, 4)
    else:
        frames = make_frames(args.seed, FRAME_SIZES, FRAMES_PER_SIZE)
        ok = one_chip_phases(svm, frames)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
