"""Readings that the limits of `compare.LIMITS` are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 4 [--control int8,float8_e4m3fn]

One process sets the cell up once, then for each seed makes that seed's
frames and drives the running service for a short window at the cell's
own load, and compares a sample of the answers with the reference, as
`run.py` does (the program's readings, the lower end of each limit).
For the control seeds it also puts the reference, computed with
descriptors and weights in each lower precision, in the program's place
on the same frames (the control's readings, the upper end), and reads
two faults of the served answers on the same sample: every other
sampled frame's answer left out (`half_left_out`) and the best box of
each answer moved by 8 px (`answer_altered`). One JSON line per seed
and reading; the last line sums them up. The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def _lower(dtype: str, cell, w64, b: float):
    """The reference in a lower precision, in the program's place."""
    from chipbench import compare, reference

    def answer(n, rec, frame):
        return compare.served_from_reference(reference.detect(
            frame, w64, b, blocks_dtype=dtype,
            **compare.reference_args(cell.config, frame.shape[:2])))
    return answer


def _half(n, rec, frame):
    return rec.payload["detections"] if n % 2 == 0 else []


def _moved(n, rec, frame):
    dets = rec.payload["detections"]
    if not dets:
        return dets
    y0, x0, y1, x1 = dets[0]["box"]
    return [dict(dets[0], box=(y0, x0 + 8.0, y1, x1 + 8.0))] + dets[1:]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="int8,float8_e4m3fn")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    bench._environment()
    import jax

    from chipbench import cells, compare
    if jax.devices()[0].platform != "tpu":
        log("control: runs only on the chip")
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cells.cell(args.workload)
    session, w64, b = bench.build(cell, "", log)
    svc = session.serve().start()
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    summary: dict = {}
    try:
        bench.warm_service(svc, cell, bench.frames(cell, 0, log)[0][0], log)
        for seed in (int(s) for s in args.seeds.split(",")):
            clip_frames = bench.frames(cell, seed, log)
            win = bench.measure(svc, cell, clip_frames, seed, args.seconds,
                                log)
            failed = sum(not r.ok for r in win.records)
            runs = [("program", None)]
            if seed in ctl_seeds:
                runs += [(c, _lower(c, cell, w64, b))
                         for c in args.control.split(",")]
                runs += [("half_left_out", _half), ("answer_altered", _moved)]
            for name, answer in runs:
                per_frame = bench.check(win, cell, clip_frames, seed, w64,
                                        b, log, answer=answer, label=name)
                got = compare.combine(per_frame, failed)
                line = {"seed": seed, "reading": name,
                        **{k: v["value"] for k, v in got.items()},
                        "boxes": sum(f["boxes"] for f in per_frame),
                        "ref_boxes": sum(f["ref_boxes"] for f in per_frame),
                        "e2e": bench.end_to_end(win, cell.traffic)}
                print(json.dumps(line), flush=True)
                s = summary.setdefault(name, {})
                for k in compare.LIMITS:
                    s.setdefault(k, []).append(line[k])
    finally:
        svc.stop()
    print(json.dumps({"summary": {
        name: {k: {"min": min(v), "max": max(v)} for k, v in s.items()}
        for name, s in summary.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    t0 = time.monotonic()
    rc = main()
    print(f"control: {time.monotonic() - t0} s", file=sys.stderr)
    sys.exit(rc)
