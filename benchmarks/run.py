"""Benchmark runner: one section per paper table + framework benches.
Prints ``name,value,derived`` CSV rows. ``--fast`` trims sizes for CI.
A failed section is reported and the rest still run; the exit code is
nonzero when any section failed.
"""
import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="accuracy|timing|kernels|roofline|train")
    args = ap.parse_args()

    from benchmarks import (bench_accuracy, bench_kernels, bench_roofline,
                            bench_timing, bench_train)
    benches = {
        "accuracy": lambda: bench_accuracy.run(fast=args.fast),
        "timing": lambda: bench_timing.run(fast=args.fast),
        "kernels": lambda: bench_kernels.run(fast=args.fast),
        "train": lambda: bench_train.run(fast=args.fast),
        "roofline": lambda: bench_roofline.run(),
    }
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"\n== bench:{name} ==", flush=True)
        try:
            fn()
        except Exception as e:  # report, keep going
            print(f"{name}/FAILED,{e!r},", file=sys.stderr)
            print(f"{name}/FAILED,{e!r},")
            failed.append(name)
        print(f"{name}/bench_wall_s,{time.time()-t0:.1f},")
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
