"""From a profiler trace to the numbers the per-layer metrics read.

`events(path)` reads the device operations out of the `.xplane.pb` that
`jax.profiler` wrote, and `reduce(events, window)` turns them into a
`Summary`: device busy time (the union of the intervals in which an
operation ran on a TPU, averaged over the chips), the window, each
device operation's summed self time, and the idle gaps, each labelled
by the benchmark's own host span that overlaps it most. The window and
the host spans come from the benchmark, on the trace's clock
(`run.Tracer`): the trace is taken with the host tracer off. Everything
after `events` is plain Python on those lists, so the tests run it on
hand-made events and on a trimmed trace recorded on the chip.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

#: the device line whose events are operations
DEVICE_LINE = "XLA Ops"
#: host spans that label idle gaps: what the benchmark was doing
LABEL_SPANS = ("chipbench.submit", "chipbench.wait")
#: idle gaps kept, longest first
N_GAPS = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction's name of a device event. The TPU's trace
    names each operation by its whole HLO text (`%fusion.12 = f32[...]
    fusion(...), ...`); the name is the text before the first space."""
    return event_name.split(" ", 1)[0].lstrip("%")


def events(path: str) -> List[list]:
    """[[plane, op name, start_ns, dur_ns], ...]: the operations of every
    TPU plane, on the trace's clock (ns from the trace's start)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    dev += [[plane.name, op_name(e.name), e.start_ns,
                             e.duration_ns] for e in line.events]
    return dev


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]    # ns, host clock
    window_s: float
    busy_s: float                  # mean over chips
    chips: int
    op_s: Dict[str, float]         # summed self time per op name
    gaps: List[Tuple[str, float]]  # (label, seconds), longest first

    def kernel_s(self, pattern: str) -> float:
        """Summed device time of the ops whose name matches `pattern`."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def reduce(device: List[list], host: List[list],
           window: Tuple[float, float]) -> Summary:
    """`device`: [[plane, op name, start_ns, dur_ns], ...]; `host`:
    [[span name, start_ns, dur_ns], ...]; `window`: (start_ns, end_ns),
    all on one clock."""
    lo, hi = window
    if not hi > lo:
        raise ValueError(f"the traced window {window} holds no time")
    per_plane: Dict[str, list] = collections.defaultdict(list)
    for plane, name, s, d in device:
        iv = _clip([(s, s + d)], lo, hi)
        if iv:
            per_plane[plane].append((iv[0][0], iv[0][1], name))
    op_s = _self_s(per_plane)
    chips = max(1, len(per_plane))
    busy = {p: union([(s, e) for s, e, _ in iv])
            for p, iv in per_plane.items()}
    busy_s = sum(e - s for iv in busy.values() for s, e in iv) / 1e9 / chips
    labels = [(name, s, s + d) for name, s, d in host
              if name in LABEL_SPANS]
    gaps = []
    for iv in busy.values():
        edges = [lo] + [t for s, e in iv for t in (s, e)] + [hi]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:N_GAPS]
    return Summary((lo, hi), (hi - lo) / 1e9, busy_s, chips, dict(op_s),
                   [(_label(labels, s, e), (e - s) / 1e9) for s, e in gaps])


def _self_s(per_plane: Dict[str, list]) -> Dict[str, float]:
    """Summed self time of each operation name, in seconds. A loop's
    event (`while.N`) spans the operations of its body on the same
    line; its self time is what its children leave uncovered."""
    out: Dict[str, float] = collections.defaultdict(float)
    for iv in per_plane.values():
        stack: List[list] = []              # [end, name, self_ns]
        for s, e, name in sorted(iv, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][0] <= s:
                _, n, own = stack.pop()
                out[n] += own / 1e9
            if stack:
                stack[-1][2] -= min(e, stack[-1][0]) - s
            stack.append([e, name, e - s])
        for _, n, own in stack:
            out[n] += own / 1e9
    return dict(out)


def _label(labels, s, e) -> str:
    best, name = 0.0, "no benchmark span"
    for n, ls, le in labels:
        ov = min(e, le) - max(s, ls)
        if ov > best:
            best, name = ov, n
    return name
