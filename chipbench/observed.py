"""What one traced run observed, as the per-layer readers see it.

Each reader (chipbench/metrics/<metric>.py) has `read(obs)` returning a
number, or None where the run holds nothing for it to read: the harness
then leaves the metric out of the result line. A share of a roofline or
a peak is never reported as 0 for want of data.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from chipbench.trace_reduce import Summary


@dataclasses.dataclass
class Observed:
    seconds: float                         # the measured window
    end_to_end: Dict[str, float]           # this run's end-to-end values
    engine: Dict[str, float]               # service counters over the window
    stage_timing: List[dict]               # engine stage_timing events
    trace: Optional[Summary]               # None without a trace
    trace_frames: int                      # frames answered in the trace
    costs: Dict[str, Tuple[int, int]]      # kernel -> (ops, bytes) per frame
    peak: Dict[str, float]                 # this chip's peaks

    @property
    def ops_per_frame(self) -> int:
        return sum(ops for ops, _ in self.costs.values())

    def least_s(self, kernel: str) -> Tuple[float, str]:
        """Least time one frame's work of `kernel` can take on this
        chip, and which bound sets it ("ops" or "bytes")."""
        ops, byts = self.costs[kernel]
        t_ops = ops / self.peak["bf16_flops"]
        t_bytes = byts / self.peak["hbm_bytes_per_s"]
        return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")

    def roofline(self, kernel: str, pattern: str) -> Optional[float]:
        """% of the roofline: the least time of the traced frames' work
        over the summed device time of the ops matching `pattern`. A
        trace with device ops of which none matches is a fault of the
        pattern, not a run with nothing to read: it raises."""
        if self.trace is None or not self.trace_frames \
                or self.trace.busy_s <= 0:
            return None
        spent = self.trace.kernel_s(pattern)
        if spent <= 0:
            raise LookupError(
                f"no device op of the trace matches {pattern!r} (the "
                f"{kernel} kernel); ops: {sorted(self.trace.op_s)[:20]}")
        return 100.0 * self.least_s(kernel)[0] * self.trace_frames / spent

    def device_ms_per_frame(self) -> Optional[float]:
        if self.trace is None or self.trace.busy_s <= 0 \
                or not self.trace_frames:
            return None
        return 1e3 * self.trace.busy_s / self.trace_frames

    def idle_share(self) -> Optional[float]:
        if self.trace is None or self.trace.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)
