"""One run of one benchmark cell, on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's `DetectionSession(svm, config)` from its
configuration file, compiles (or loads from JAX's persistent cache in
`<checkout>/.jax_cache`) the program of every batch size the cell's
traffic can form, makes each stream's frames from the seed, starts the
session's `serve()` service and sends each of those batch sizes through
it once. The window then drives `DetectionService.submit_frame` from
the traffic generator (chipbench/traffic.py) for `--seconds`. Latency
is taken on the client side, from when each frame was due until its
answer arrived.

After the window the service stops and a sample of the answers, drawn
from the seed, is checked against the plain reference
(chipbench/compare.py). With `--trace 1` the window runs under the JAX
profiler, its host tracer off (`Tracer`), and the per-layer metrics are
read from the trace, the engine's counters and its stage events; with
`--trace 0` the end-to-end metrics are reported.

The last line of standard output is one JSON object: correct,
attempted, failed, metrics, device, with `--trace 1` breakdown, and
last the compared numbers beside their limits, which are also the last
lines of standard error. The run refuses (exit 2, no result) unless JAX's
first device is a TPU listed in chipbench/peaks.json and there are as
many chips as the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _environment() -> None:
    """Before JAX starts: the compile cache at a fixed path inside the
    checkout, and no autotune decisions read from or written to disk."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["REPRO_AUTOTUNE_CACHE"] = ""


def quantile(values: List[float], q: float) -> float:
    """Nearest-rank quantile."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def pipeline_config(cfg: dict, events_path: str = ""):
    from repro.api import presets
    from repro.obs.metrics import MetricsConfig
    base = presets(cfg["preset"])
    det = {k: tuple(v) if isinstance(v, list) else v
           for k, v in cfg["detector"].items()}
    svc = dict(cfg["service"])
    if events_path:
        svc["metrics"] = MetricsConfig(jsonl_path=events_path,
                                       stage_timing=True)
    return base.replace(
        detector=dataclasses.replace(base.detector, **det),
        service=dataclasses.replace(base.service, **svc))


def load_svm(cfg: dict):
    d = json.loads((ROOT / cfg["svm"]).read_text())
    return d["w"], d["b"]


class _Compiles:
    """Counts XLA compilations while `on`."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.on, self.n, self.s = False, 0, 0.0
        self._event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if self.on and event == self._event:
            self.n += 1
            self.s += duration

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def build(cell, events_path: str, log: Callable[[str], None]):
    """The cell's session with every program its traffic can call
    compiled and run once; returns (session, svm weights, svm bias)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.api import DetectionSession
    cfg, mix = cell.config, cell.traffic
    h, w = int(cfg["frame"]["h"]), int(cfg["frame"]["w"])
    w_list, b = load_svm(cfg)
    session = DetectionSession(
        {"w": jnp.asarray(np.asarray(w_list, np.float32)),
         "b": jnp.asarray(np.float32(b))},
        pipeline_config(cfg, events_path))
    t = time.monotonic()
    session.warmup([(h, w) if n == 1 else (n, h, w)
                    for n in mix["batch_sizes"]])
    log(f"setup: programs for batch sizes {mix['batch_sizes']} ready in "
        f"{time.monotonic() - t} s")
    return session, np.asarray(w_list, np.float64), float(b)


def frames(cell, seed: int, log: Callable[[str], None]):
    t = time.monotonic()
    cfg, mix = cell.config, cell.traffic
    from chipbench import traffic
    out = traffic.clips(mix, int(cfg["frame"]["h"]), int(cfg["frame"]["w"]),
                        seed)
    log(f"setup: {len(out)} clips of {mix['clip']['frames']} frames made "
        f"in {time.monotonic() - t} s")
    return out


def warm_service(svc, cell, frame, log: Callable[[str], None]) -> None:
    """Send each batch size the cell's traffic can form through the
    started service, `n` frames at once, until the service has answered
    them as one batch: a batch's answers are sliced and decoded per
    frame by small programs of their own, which `warmup` does not
    reach. Whatever a size needs is then compiled or loaded before the
    window."""
    for n in sorted(cell.traffic["batch_sizes"]):
        for _ in range(5):
            before = svc.stats["frame_batches"]
            for f in [svc.submit_frame(frame) for _ in range(n)]:
                f.get(timeout=600)
            if svc.stats["frame_batches"] - before == 1:
                break
        else:
            log(f"setup: the service never answered {n} frames as one "
                f"batch")


class Tracer:
    """The JAX profiler over the window, with its host tracer off.

    The host tracer records the runtime's own work for every device
    operation, and that slowed the service several times over on a TPU
    v5e (PERF.md). Off, the trace holds the device's operations alone,
    on a clock of its own that starts with the trace. `start()` runs one
    tiny program on the idle device as its first operation, and that
    operation ties the trace's clock to the host's `perf_counter`."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._tick = jax.jit(lambda x: x + 1)
        self._x = jnp.zeros((), jnp.float32)
        self._tick(self._x).block_until_ready()      # compiled in set-up
        self.t_tick = float("nan")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_tick = time.perf_counter()
        self._tick(self._x).block_until_ready()

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def read(self, win: "Window"):
        """The trace reduced over the window, from its open to the last
        answer, with the benchmark's host spans on the trace's clock."""
        from chipbench import trace_reduce, traffic
        device = trace_reduce.events(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        if not device:
            raise RuntimeError("the trace holds no device operation")
        tick = min(s for _, _, s, _ in device)
        device = [ev for ev in device if ev[2] > tick]

        def ns(t: float) -> float:
            return tick + 1e9 * (t - self.t_tick)
        host = [[n, ns(a), 1e9 * (b - a)]
                for n, a, b in traffic.spans(win.all_records)]
        end = max([r.done for r in win.all_records if r.done == r.done],
                  default=win.t_open + win.seconds)
        return trace_reduce.reduce(device, host, (ns(win.t_open), ns(end)))


@dataclasses.dataclass
class Window:
    records: list                 # every frame of the window
    all_records: list             # with the closed loop's drain
    t_open: float
    seconds: float
    before: dict                  # service counters at the open
    after: dict                   # ... and after the last answer
    frame_target: int
    compiles: int
    compile_s: float
    memory_peak_bytes: Optional[int]


def measure(svc, cell, clip_frames, seed: int, seconds: float,
            log: Callable[[str], None], t_open: Optional[float] = None
            ) -> Window:
    """Drive the running service for one window from the cell's mix."""
    import jax

    from chipbench import traffic
    mix = cell.traffic
    compiles = _Compiles()
    before = dict(svc.stats)
    if t_open is None:
        t_open = time.perf_counter() + 0.05
    compiles.on = True
    time.sleep(max(0.0, t_open - time.perf_counter()))
    try:
        if mix["kind"] == "open":
            recs = traffic.run_open(
                svc.submit_frame, clip_frames,
                traffic.open_schedule(mix, seed, seconds), t_open, seconds)
        else:
            recs = traffic.run_closed(
                svc.submit_frame, clip_frames, int(mix["in_flight"]),
                t_open, seconds)
    finally:
        compiles.on = False
        compiles.close()
    t_close = t_open + seconds
    window = recs if mix["kind"] == "open" else \
        [r for r in recs if r.sent < t_close]
    dev = jax.devices()[0]
    w = Window(window, recs, t_open, seconds, before, dict(svc.stats),
               svc.frame_target, compiles.n, compiles.s,
               (dev.memory_stats() or {}).get("peak_bytes_in_use"))
    late = [1e3 * (r.sent - r.due) for r in window]
    log(f"generator: {len(window)} frames sent, late by p50 "
        f"{quantile(late, 0.5)} ms, p95 {quantile(late, 0.95)} ms, max "
        f"{max(late)} ms")
    ready = [1e3 * (b.done - a.done) for a, b in zip(recs, recs[1:])
             if b.ready_on_arrival]
    log(f"collector: {len(ready)} of {len(recs)} answers were waiting when "
        f"the in-order collector reached them; it reached each at most "
        f"{max(ready, default=0.0)} ms after the previous one")
    log(f"compilations inside the window: {w.compiles} ({w.compile_s} s)")
    d = {k: w.after[k] - w.before[k]
         for k in ("frames", "frame_batches", "frames_saturated",
                   "batch_fallbacks", "frame_errors")}
    log(f"service: {d['frames']} frames in {d['frame_batches']} batches, "
        f"{d['frames_saturated']} saturated top-k, {d['batch_fallbacks']} "
        f"batch fallbacks, {d['frame_errors']} errors")
    return w


def end_to_end(win: Window, mix: dict) -> Dict[str, float]:
    from chipbench import traffic
    if mix["kind"] == "open":
        until = win.t_open + win.seconds + traffic.ANSWER_GRACE_S
        lat = [1e3 * ((r.done if r.ok else until) - r.due)
               for r in win.records]
        return {"frame_p50_ms": quantile(lat, 0.50),
                "frame_p95_ms": quantile(lat, 0.95)}
    t_close = win.t_open + win.seconds
    done = [r for r in win.records if r.ok and r.done <= t_close]
    return {"frames_per_s": len(done) / win.seconds}


def check(win: Window, cell, clip_frames, seed: int, w64, b: float,
          log: Callable[[str], None], answer: Optional[Callable] = None,
          label: str = ""):
    """Per-frame numbers of the answers sampled from the seed against
    the reference. `answer(i, record, frame)` may stand in for the served
    answer of the i-th sampled frame (the control and the faults of
    chipbench/control.py)."""
    from chipbench import compare, traffic
    cfg = cell.config
    t = time.monotonic()
    answered = [r for r in win.records if r.ok]
    per_frame = []
    for n, i in enumerate(traffic.check_sample(
            len(answered), cell.traffic["check_frames"], seed)):
        r = answered[i]
        clip = clip_frames[r.stream]
        frame = clip[r.frame % len(clip)]
        dets = r.payload["detections"] if answer is None \
            else answer(n, r, frame)
        per_frame.append(compare.check_frame(dets, frame, w64, b, cfg))
    log(f"check{' (' + label + ')' if label else ''}: "
        f"{len(per_frame)} frames against the reference in "
        f"{time.monotonic() - t} s; served boxes "
        f"{sum(f['boxes'] for f in per_frame)}, reference boxes "
        f"{sum(f['ref_boxes'] for f in per_frame)}, candidates per frame "
        f"{[f['candidates'] for f in per_frame]}")
    return per_frame


def per_layer(cell, win: Window, e2e: dict, summary, stage: list,
              peak: dict) -> dict:
    from chipbench import cells
    from chipbench.observed import Observed
    cfg = cell.config
    h, w = int(cfg["frame"]["h"]), int(cfg["frame"]["w"])
    engine = {k: win.after[k] - win.before[k]
              for k in ("frames", "frame_batches")}
    engine["frame_target"] = win.frame_target
    obs = Observed(
        seconds=win.seconds, end_to_end=e2e, engine=engine,
        stage_timing=stage, trace=summary,
        trace_frames=sum(r.ok for r in win.all_records),
        costs={k: m.cost(h, w, cfg["detector"], cfg["precision"])
               for k, m in cells.costs(cfg["costs"]).items()},
        peak=peak)
    out = {}
    for m in cell.per_layer:
        v = cells.metric_reader(m["name"]).read(obs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out, obs


def run(cell, seed: int, seconds: float, trace: bool, peak: dict,
        t0: float, log: Callable[[str], None],
        patch: Optional[Callable] = None) -> dict:
    """Set up, measure, check; returns the result object. `patch(svc)`
    may alter the service before the window (the fault tests)."""
    import jax

    from chipbench import compare
    events_path = ""
    if trace:
        fd, events_path = tempfile.mkstemp(prefix="chipbench-",
                                           suffix=".jsonl")
        os.close(fd)
    session, w64, b = build(cell, events_path, log)
    clip_frames = frames(cell, seed, log)
    t_svc = time.perf_counter()
    svc = session.serve()
    if patch is not None:
        patch(svc)
    svc.start()
    tracer = None
    try:
        warm_service(svc, cell, clip_frames[0][0], log)
        if trace:
            tracer = Tracer()
            tracer.start()
        t_open = time.perf_counter() + 0.05
        setup_s = time.monotonic() + (t_open - time.perf_counter()) - t0
        try:
            win = measure(svc, cell, clip_frames, seed, seconds, log, t_open)
        finally:
            if tracer is not None:
                tracer.stop()
    finally:
        svc.stop()
    e2e = end_to_end(win, cell.traffic)
    e2e["setup_s"] = setup_s
    dev = jax.devices()[0]
    result: dict = {"correct": False, "attempted": len(win.records),
                    "failed": sum(not r.ok for r in win.records),
                    "metrics": {}, "device": {
                        "platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": win.memory_peak_bytes}}
    if trace:
        summary = tracer.read(win)
        with open(events_path) as f:
            stage = [ev for ev in map(json.loads, f)
                     if ev.get("kind") == "stage_timing"
                     and ev["t_ms"] >= 1e3 * (t_open - t_svc)]
        os.unlink(events_path)
        result["metrics"], obs = per_layer(cell, win, e2e, summary, stage,
                                           peak)
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        for k in ("hog", "score"):
            least, bound = obs.least_s(k)
            log(f"roofline: {k} least {1e3 * least} ms a frame, bound by "
                f"{bound}")
    else:
        result["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    del session, svc
    per_frame = check(win, cell, clip_frames, seed, w64, b, log)
    result["checks"] = compare.combine(per_frame, result["failed"])
    result["correct"] = compare.passed(result["checks"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    _environment()
    import jax

    from chipbench import cells
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chipbench: JAX's first device is {dev.platform!r}, not a TPU; "
            f"the benchmark runs only on the chip")
        return 2
    cell = cells.cell(args.workload)
    if len(devices) < cell.chips:
        log(f"chipbench: {args.workload} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    try:
        peak = cells.peaks(dev.device_kind)
    except KeyError as e:
        log(f"chipbench: {e.args[0]}")
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), peak,
                     T0, log)
    except Exception:
        log(traceback.format_exc())
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
