"""Detection-service launcher: builds a repro.api DetectionSession
(training a quick SVM or loading one with --load), starts
session.serve() -- the micro-batching DetectionService -- streams
synthetic frames through it, and prints per-frame latency, saturation,
and service stats. It exits nonzero when any frame came back with an
error.

    PYTHONPATH=src python -m repro.launch.serve [--frames 6]
        [--preset paper] [--load DIR]

`--chaos` replays the standard fault-injection schedule
(serve/faults.py chaos_specs: worker kill, device loss, latency
spikes) through the supervised engine and exits nonzero unless every
submitted frame resolved -- the CLI face of the chaos-smoke CI lane.
`--metrics PATH` streams the service's structured telemetry
(DESIGN.md §15 event schema) to a JSONL file you can `tail -f`.
"""
from __future__ import annotations

from repro import platform  # noqa: F401  (applies REPRO_* before jax init)

import argparse
import sys
import time


def _detect_smoke(args) -> int:
    import numpy as np

    from repro.api import DetectionSession, PipelineConfig, presets
    from repro.core.detector import DetectorConfig
    from repro.core.svm import SVMTrainConfig
    from repro.data.synth_pedestrian import make_scene

    if args.preset:
        cfg = presets(args.preset)
    else:
        cfg = PipelineConfig(
            detector=DetectorConfig(score_threshold=0.5),
            train=SVMTrainConfig(steps=1200, neg_weight=6.0))

    session = None
    if args.load:
        try:
            session = DetectionSession.load(args.load, cfg)
            print(f"loaded SVM params from {args.load}")
        except FileNotFoundError:
            print(f"no checkpoint under {args.load}; training")
    if session is None:
        print(f"training a quick SVM ({cfg.train.steps} steps) ...")
        session = DetectionSession.train(cfg, n_pos=500, n_neg=350)

    opts = {}
    if args.chaos:
        from repro.serve.faults import FaultInjector, chaos_specs
        opts["faults"] = FaultInjector(chaos_specs(), seed=0)
        print("chaos: injecting worker-kill, device-loss, and latency "
              "faults (serve/faults.py chaos_specs)")
    if args.metrics:
        from repro.obs import MetricsConfig
        opts["metrics"] = MetricsConfig(jsonl_path=args.metrics, ring=64)
        print(f"metrics: streaming JSONL events to {args.metrics} "
              f"(tail -f it in another terminal)")
    service = session.serve(**opts).start()
    rng = np.random.default_rng(0)
    frames = [make_scene(rng, 240, 320, n_people=2)[0]
              for _ in range(args.frames)]
    print(f"streaming {args.frames} 320x240 frames through "
          f"session.serve() ...")
    t0 = time.time()
    results = service.detect_frames(frames)
    wall = time.time() - t0
    ms = [r["ms"] for r in results]
    n_sat = sum(bool(r.get("saturated")) for r in results)
    n_box = sum(len(r["detections"]) for r in results)
    n_err = sum("error" in r for r in results)
    if len(ms) > 1:
        print(f"wall          {wall:.2f}s  first={ms[0]:.0f} ms "
              f"(compile), steady={np.mean(ms[1:]):.0f} ms")
    else:
        print(f"wall          {wall:.2f}s")
    print(f"boxes         {n_box} total, {n_sat} frames top-k saturated")
    s = service.stats
    print(f"service stats frames={s['frames']} "
          f"batches={s['frame_batches']} "
          f"occupancy={s['frame_occupancy']:.2f}")
    lat = s["latency_ms"]
    print(f"resilience    p50={lat['p50']:.0f}ms p99={lat['p99']:.0f}ms "
          f"shed={s['deadline_shed']} retries={s['retries']} "
          f"restarts={s['restarts']} "
          f"breaker={s['breaker']['state']} rung={s['degraded_mode']}")
    plat = s["platform"]
    print(f"platform      {plat['backend']} x{plat['device_count']} "
          f"x64={plat['x64']} jax={plat['jax_version']}")
    service.stop()
    if args.metrics:
        from repro.obs import JsonlSink
        events = JsonlSink.read(args.metrics)
        by_kind = {}
        for e in events:
            by_kind[e["kind"]] = by_kind.get(e["kind"], 0) + 1
        kinds = " ".join(f"{k}={n}" for k, n in sorted(by_kind.items()))
        print(f"metrics       {len(events)} events: {kinds}")
    if args.chaos:
        # liveness gate: every future resolved, chaos or not
        resolved = s["frame_answers"] == len(frames)
        print(f"chaos         fired={opts['faults'].fired} "
              f"errors={n_err} all_resolved={resolved}")
        return 0 if resolved else 1
    if n_err:
        print(f"{n_err} of {len(frames)} frames came back with an error",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=6,
                    help="frames to stream through the service")
    ap.add_argument("--preset", default=None,
                    help="PipelineConfig preset")
    ap.add_argument("--chaos", action="store_true",
                    help="run under the standard fault-"
                         "injection schedule (worker kill, device "
                         "loss, latency spikes) and gate on liveness")
    ap.add_argument("--load", metavar="DIR", default=None,
                    help="restore SVM params from a "
                         "checkpoint dir instead of training")
    ap.add_argument("--metrics", metavar="PATH", default=None,
                    help="stream service telemetry as JSONL "
                         "events to PATH (DESIGN.md §15 schema)")
    return _detect_smoke(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
