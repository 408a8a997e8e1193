"""The program's own spans and named scopes, read beside the device trace.

The program records in-memory spans on the host's `perf_counter`
(repro.obs.spans: `serve.batch` and its children in the service,
`detect.*` in the detector and its results, `host.gc`, `jax.compile`)
and names each stage of its frame program with `jax.named_scope`
(`STAGES` below), which the compiled program carries as each
operation's `op_name` (the v5e's trace does not: the readers take it
from the compiled text, repro.core.detector.op_scopes). This module
turns them into:

  - device time by stage: each operation's self time summed by the
    innermost stage named in its `op_name`; operations that carry none
    are `unscoped`, and operations of other programs (a batch's answer
    slicing, the trace's tick) go by their module;
  - idle-gap labels: each gap of the device goes to the program span
    that covers most of it with its own time (children's time goes to
    the children); `host.gc` or `jax.compile` wins where one overlaps
    the gap; `serve.idle` where the service's worker held no batch; the
    benchmark's own spans where the program recorded none;
  - host time by stage: each span name's self time over the window;
  - the readings of the per-layer metrics that rest on them
    (chipbench/metrics/batch_idle_share.py, service_p95_ms.py,
    program_load_s.py and the <stage>_ms_per_frame readers).

Everything here works on plain lists, so the tests run it on hand-made
events. Where the program records no spans or carries no scopes (an
older program), the readers return None.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import trace_reduce

#: the frame program's stages, as its named scopes spell them
STAGES = ("gray", "resize", "hog", "score/matmul", "score/collate",
          "select", "nms")
#: program spans that explain a stall wherever they overlap a gap
STALLS = ("host.gc", "jax.compile")
#: spans that time a request's whole life, not a stage of the worker
REQUEST_SPANS = ("serve.request",)
#: the worker's span around one batch
BATCH_SPAN = "serve.batch"
#: the device line whose events are the programs (modules) that ran
MODULE_LINE = "XLA Modules"
IDLE = "serve.idle"
UNSCOPED = "unscoped"

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


# ------------------------------------------------------------- stages
def stage(op_name: str) -> Optional[str]:
    """The innermost stage named in an `op_name` scope path, or None.
    Transform wrappers are looked through (`vmap(nms)` is `nms`); an
    op_name the compiler merged from several (`a/x;b/y`) is read in
    order, the last stage found winning."""
    found = None
    for path in op_name.split(";"):
        parts = []
        for comp in path.split("/"):
            m = _WRAPPED.match(comp)
            while m:
                comp = m.group(1)
                m = _WRAPPED.match(comp)
            parts.append(comp)
        for i, comp in enumerate(parts):
            if comp == "score" and i + 1 < len(parts) \
                    and f"score/{parts[i + 1]}" in STAGES:
                found = f"score/{parts[i + 1]}"
            elif comp in STAGES:
                found = comp
    return found


def stage_s(op_s: Dict[str, float], scopes: Dict[str, str],
            modules: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """Seconds by stage from per-operation self time (`op_s`), an
    operation's `op_name` (`scopes`) and, optionally, the module each
    operation ran in: an operation outside the frame program's scopes
    goes under its module where one is known, else `unscoped`."""
    out: Dict[str, float] = collections.defaultdict(float)
    for op, s in op_s.items():
        st = stage(scopes.get(op, ""))
        if st is None:
            st = (modules or {}).get(op) or UNSCOPED
        out[st] += s
    return dict(out)


def has_scopes(scopes: Dict[str, str]) -> bool:
    return any(stage(v) is not None for v in scopes.values())


def event_stage_s(device: List[list], window: Tuple[float, float],
                  by_op: bool = False) -> Dict[str, float]:
    """Device self seconds by stage over the window, from operations
    that carry their module and `op_name`: [[plane, op, start_ns,
    dur_ns, module, op_name], ...] (`read_events`). An operation without
    a stage is `unscoped` in a module that has staged operations (the
    frame program) and goes by its module elsewhere. `by_op` keys the
    result `<stage>/<op>` instead."""
    lo, hi = window
    framed = {ev[4] for ev in device if stage(ev[5]) is not None}
    per_plane: Dict[str, list] = collections.defaultdict(list)
    for plane, op, s, d, module, op_name in device:
        iv = trace_reduce._clip([(s, s + d)], lo, hi)
        if iv:
            st = stage(op_name) or (UNSCOPED if module in framed
                                    else module or UNSCOPED)
            per_plane[plane].append(
                (iv[0][0], iv[0][1], f"{st}/{op}" if by_op else st))
    return trace_reduce._self_s(per_plane)


def read_events(path: str, op_names: Optional[Dict[str, str]] = None,
                frame_module: str = "") -> List[list]:
    """[[plane, op, start_ns, dur_ns, module, op_name], ...]: each TPU
    operation of a `.xplane.pb` with the module it ran in (the plane's
    module line, by time). The v5e's trace carries no `op_name` on its
    operations; an op of `frame_module` takes its own from `op_names`
    (`repro.core.detector.op_scopes` of the program that ran)."""
    import bisect

    from jax.profiler import ProfileData
    op_names = op_names or {}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                               e.name.split("(")[0]) for e in line.events)
            elif line.name == trace_reduce.DEVICE_LINE:
                ops = [(trace_reduce.op_name(e.name), e.start_ns,
                        e.duration_ns) for e in line.events]
        starts = [m[0] for m in mods]
        for op, s, d in ops:
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
            name = op_names.get(op, "") if module == frame_module else ""
            out.append([plane.name, op, s, d, module, name])
    return out


# ------------------------------------------------------- program spans
def on_trace_clock(spans: Iterable, t_tick: float, tick_ns: float
                   ) -> List[list]:
    """[[name, start_ns, end_ns, id, parent, batch], ...]: spans (the
    recorder's `Span`s, on `perf_counter`) on the trace's clock, whose
    tick operation started at `tick_ns` when the host read `t_tick`
    (chipbench/run.py: Tracer)."""
    return [[s.name, tick_ns + 1e9 * (s.start - t_tick),
             tick_ns + 1e9 * (s.end - t_tick), s.id, s.parent, s.batch]
            for s in spans]


def label_gap(s: float, e: float, over: List[list],
              bench: List[Tuple[str, float, float]],
              recorded: bool = True) -> str:
    """The label of the idle gap [s, e) (see the module docstring).
    `over`: the program's spans on the trace clock (`on_trace_clock`)
    that overlap the gap, request spans left out; `bench`: (name, start,
    end) of the benchmark's own spans, read where the program recorded
    no spans at all (`recorded` False)."""
    if not recorded:
        best, name = 0.0, "no benchmark span"
        for n, ls, le in bench:
            ov = min(e, le) - max(s, ls)
            if ov > best:
                best, name = ov, n
        return name
    stalls = [(min(e, p[2]) - max(s, p[1]), p[0]) for p in over
              if p[0] in STALLS]
    if stalls:
        return max(stalls)[1]
    own: Dict[int, float] = {}
    for p in over:
        own[p[3]] = own.get(p[3], 0.0) + min(e, p[2]) - max(s, p[1])
    for p in over:
        if p[4] in own:
            own[p[4]] -= min(e, p[2]) - max(s, p[1])
    held = trace_reduce.union([(max(s, p[1]), min(e, p[2])) for p in over
                               if p[0] == BATCH_SPAN])
    best, name = (e - s) - sum(b - a for a, b in held), IDLE
    for p in over:
        if own[p[3]] > best:
            best, name = own[p[3]], p[0]
    return name


def idle_gaps(device: List[list], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """Every interval of the window in which no operation ran on a
    device (per plane, as trace_reduce.reduce finds them), in order."""
    lo, hi = window
    per_plane: Dict[str, list] = collections.defaultdict(list)
    for ev in device:
        iv = trace_reduce._clip([(ev[2], ev[2] + ev[3])], lo, hi)
        if iv:
            per_plane[ev[0]] += iv
    gaps = []
    for iv in per_plane.values():
        busy = trace_reduce.union(iv)
        edges = [lo] + [t for a, b in busy for t in (a, b)] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return sorted(gaps)


def labelled_gaps(device: List[list], program: List[list],
                  bench: List[Tuple[str, float, float]],
                  window: Tuple[float, float]
                  ) -> List[Tuple[float, float, str]]:
    """(start, end, label) of every idle gap of the window, in order. A
    sweep over the gaps and the program's spans, both sorted by start,
    keeps only the spans still open at each gap."""
    spans = sorted((p for p in program if p[0] not in REQUEST_SPANS),
                   key=lambda p: p[1])
    recorded = bool(program)
    out, active, i = [], [], 0
    for s, e in idle_gaps(device, window):
        while i < len(spans) and spans[i][1] < e:
            active.append(spans[i])
            i += 1
        active = [p for p in active if p[2] > s]
        over = [p for p in active if p[1] < e]
        out.append((s, e, label_gap(s, e, over, bench, recorded)))
    return out


def label_s(gaps: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle seconds by label."""
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e, name in gaps:
        out[name] += (e - s) / 1e9
    return dict(out)


def host_self_s(program: List[list], window: Tuple[float, float]
                ) -> Dict[str, float]:
    """Self seconds of each span name inside the window: a span's time
    less what its children cover, clipped to the window."""
    lo, hi = window
    own: Dict[int, float] = {}
    name: Dict[int, str] = {}
    for p in program:
        own[p[3]] = max(0.0, min(hi, p[2]) - max(lo, p[1]))
        name[p[3]] = p[0]
    for p in program:
        if p[4] in own:
            own[p[4]] -= max(0.0, min(hi, p[2]) - max(lo, p[1]))
    out: Dict[str, float] = collections.defaultdict(float)
    for i, v in own.items():
        out[name[i]] += v / 1e9
    return dict(out)


def idle_in_batches_s(device: List[list], program: List[list],
                      window: Tuple[float, float]) -> float:
    """Seconds of the window with no device operation running while the
    worker held a batch (the trace intersected with `serve.batch`)."""
    lo, hi = window
    batches = trace_reduce.union(
        [(max(lo, p[1]), min(hi, p[2])) for p in program
         if p[0] == BATCH_SPAN and p[2] > lo and p[1] < hi])
    out = 0.0
    for s, e in idle_gaps(device, window):
        for bs, be in batches:
            out += max(0.0, min(e, be) - max(s, bs))
    return out / 1e9


# ------------------------------------------- what the readers read
def recorded_spans() -> Optional[list]:
    """The spans the program recorded in this process, or None where the
    program has no recorder (an older program) or it recorded none."""
    try:
        from repro.obs.spans import RECORDER
    except ImportError:
        return None
    spans = RECORDER.spans()
    return spans or None


def window_spans(obs, name: str, counter: str) -> Optional[list]:
    """The last `obs.engine[counter]` spans called `name`, in the order
    they ended: the window's, since the engine's counter covers the
    window and nothing is served after it."""
    n = int(obs.engine.get(counter, 0) or 0)
    spans = recorded_spans() if n else None
    if not spans:
        return None
    named = [s for s in spans if s.name == name]
    if len(named) < n:
        return None
    return named[-n:]


def batch_idle_share(obs) -> Optional[float]:
    """% of the traced window in which the worker held a batch and no
    operation ran on the device: every device operation of the window
    runs inside a `serve.batch` span (its dispatch and its fetch are
    children), so that is (batch time - busy time) / window."""
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    batches = window_spans(obs, BATCH_SPAN, "frame_batches")
    if batches is None:
        return None
    held = sum(s.end - s.start for s in batches)
    return 100.0 * (held - obs.trace.busy_s) / obs.trace.window_s


def request_ms(obs) -> Optional[List[float]]:
    """`serve.request` durations of the window's answered frames, ms."""
    reqs = window_spans(obs, "serve.request", "frames")
    if reqs is None:
        return None
    return [1e3 * (s.end - s.start) for s in reqs]


def dominant_batch(obs) -> Optional[int]:
    """The batch size that served the window's frames, on average."""
    b = obs.engine.get("frame_batches", 0)
    if not b:
        return None
    return max(1, round(obs.engine["frames"] / b))


def program_scopes(obs) -> Optional[Dict[str, str]]:
    """op name -> op_name of the program that served the window, or
    None where the program reports no scopes."""
    batch = dominant_batch(obs)
    if batch is None:
        return None
    try:
        from repro.core.detector import op_scopes
    except ImportError:
        return None
    scopes = op_scopes(batch)
    return scopes if has_scopes(scopes) else None


def stage_ms_per_frame(obs, name: str) -> Optional[float]:
    """Device self time of the operations scoped `name`, over the frames
    answered in the trace, in ms."""
    if obs.trace is None or not obs.trace_frames or obs.trace.busy_s <= 0:
        return None
    scopes = program_scopes(obs)
    if scopes is None:
        return None
    s = stage_s(obs.trace.op_s, scopes).get(name, 0.0)
    return 1e3 * s / obs.trace_frames


def program_load_s(obs) -> Optional[float]:
    """Seconds to ready the programs the cell's set-up loaded."""
    if not obs.engine.get("frame_batches"):
        return None
    try:
        from repro.core.detector import program_loads
    except ImportError:
        return None
    loads = program_loads()
    if not loads:
        return None
    return sum(r["seconds"] for r in loads)
