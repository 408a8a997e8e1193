"""The knee of an open-loop cell: the most cameras it serves in time.

    python3 chipbench/sweep.py --config hd1080_perf --traffic cams \
        --fps 30 --cameras 1,2,3,4,5,6 --seconds 10 --seed 1

One process sets the configuration up once, then for each camera count
runs one window of the open-loop mix with that many cameras at `--fps`,
and prints the frame latency's median and 95th percentile and whether a
backlog grew: the last fifth of the window's frames waiting longer, at
the median, than the first fifth by more than a frame period. The knee is
the largest count whose p95 stays within `--p95-ms` with no growing
backlog. The benchmark's own runs do not run this; PERF.md records its
result, which sets the camera count of the mix. A configuration and a
mix are named by their files, so a cell can be swept before
BENCHMARK.json holds it.
"""
from __future__ import annotations

import argparse
import copy
import json
import statistics
import sys

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--cameras", default="1,2,3,4,5,6,7,8")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--p95-ms", type=float, default=100.0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    bench._environment()
    import jax

    from chipbench import cells
    if jax.devices()[0].platform != "tpu":
        log("sweep: runs only on the chip")
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cells.Cell(f"{args.config}.{args.traffic}", 1,
                      cells.config(args.config), cells.traffic(args.traffic),
                      [], [])
    session, _, _ = bench.build(cell, "", log)
    svc = session.serve().start()
    knee = 0
    try:
        for n in (int(c) for c in args.cameras.split(",")):
            c = copy.copy(cell)
            c.traffic = dict(copy.deepcopy(cell.traffic), cameras=n,
                             fps=args.fps)
            clip_frames = bench.frames(c, args.seed, log)
            win = bench.measure(svc, c, clip_frames, args.seed,
                                args.seconds, log)
            e2e = bench.end_to_end(win, c.traffic)
            lat = [1e3 * (r.done - r.due) for r in win.records if r.ok]
            fifth = max(1, len(lat) // 5)
            growth = statistics.median(lat[-fifth:]) \
                - statistics.median(lat[:fifth])
            grows = growth > 1e3 / args.fps
            ok = e2e["frame_p95_ms"] <= args.p95_ms and not grows \
                and all(r.ok for r in win.records)
            knee = n if ok and knee == n - 1 else knee
            print(json.dumps({"cameras": n, "fps": args.fps, **e2e,
                              "backlog_growth_ms": growth,
                              "failed": sum(not r.ok for r in win.records),
                              "within": ok}), flush=True)
    finally:
        svc.stop()
    print(json.dumps({"knee_cameras": knee, "fps": args.fps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
