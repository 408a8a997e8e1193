"""The unified configuration tree of the detection API.

Before this layer every host-facing entry point carried its own config
surface (`DetectorConfig`, `HOGConfig`, `TrackerConfig`,
`SVMTrainConfig`, plus loose `DetectionService` kwargs), so composing a
deployment meant threading four dataclasses through five call sites.
`PipelineConfig` is the one tree the session facade (api/session.py)
consumes: it nests all of them plus the serving knobs, keeps the HOG
geometry single-sourced (`detector.hog` always equals `hog`), and
round-trips through plain JSON so a deployment's exact configuration
can be checked in, diffed, and shipped to a service.

Presets fold in the paper-workload variants from configs/hog_svm.py:

    presets("paper")      sector-compare binning (TPU-native default)
    presets("faithful")   CORDIC magnitude/angle + NR rsqrt datapath
    presets("perf")       bf16 descriptors through the dense-grid fused
                          Pallas backend (whole-scene HOG tiled over
                          VMEM row slabs + MXU matmul scoring with f32
                          accumulation) and autotuned batch scheduling
                          (batch_chunk=0: scan-vs-vmap probed per
                          (bucket, B) at first use)
    presets("sharded")    every visible device on the batch axis
                          (detector.data_parallel=0 resolves to
                          jax.device_count() at first use): detect_batch
                          / stream / serve shard B/n_devices frames per
                          chip over the 'data' mesh, autotuned per-device
                          schedule -- the multi-device serving default
    presets("uhd")        intra-frame parallelism for big frames:
                          frames >= 1280x720 split their pyramid over
                          every visible device (detector.frame_parallel=0,
                          row-slab tiles, banded resize) with an exact
                          top-k merge -- single-frame UHD latency path;
                          byte-identical to untiled on CPU host devices
                          (tests/test_tiled.py); on TPU chips the same
                          boxes, with float scores that may differ in
                          their low bits (DESIGN.md §11)
    presets("quant")      the paper's fixed-point datapath end to end:
                          integer CORDIC gradient/bin unit, int16 cell
                          histograms, int8 block descriptors with
                          per-block scale, int8x int8->int32 MXU scoring
                          (HOGConfig.numerics="fixed", DESIGN.md §12).
                          Accuracy within 1.5 points of fp32 on the
                          paper's Table I split (bench_accuracy.py);
                          byte-identical under data/tile sharding
    presets("cascade")    two-stage scheduling: the half-resolution
                          coarse head rejects empty neighbourhoods at a
                          loose threshold and the full dense chain runs
                          only on surviving snapped crops, with
                          tracker-predicted boxes promoted past the
                          coarse gate on video (core/cascade.py,
                          DESIGN.md §13)
    presets("resilient")  the serving-SLO variant: 500 ms per-request
                          deadlines, supervised-worker retry/backoff, a
                          5-failure circuit breaker, and the cascade-
                          backed degradation ladder (p99 >= 120 ms or
                          32 pending frames drops a rung; DESIGN.md §14)
    presets("default")    the plain DetectorConfig defaults

`presets()` lists the registered names; `register_preset` adds
deployment-local ones.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple

from repro.configs import hog_svm
from repro.core.cascade import CascadeConfig
from repro.core.detector import DetectorConfig
from repro.core.hog import HOGConfig, PAPER_HOG
from repro.core.svm import SVMTrainConfig
from repro.core.video import TrackerConfig
from repro.obs.metrics import MetricsConfig
from repro.serve.resilience import ResilienceConfig, RetryPolicy


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the DetectionService front-end (serve/engine.py)."""

    window_batch: int = 64        # padded micro-batch of the window path
    max_wait_ms: float = 2.0      # straggler deadline when coalescing
    frame_batch: int = 8          # frames per batched detection step
    max_pending_frames: int = 256  # backpressure bound (ServiceOverloaded)
    # deadlines / retry / breaker / degradation ladder (DESIGN.md §14);
    # the defaults are inert -- supervision and transient retry are
    # always on, deadlines and the ladder only when configured
    resilience: ResilienceConfig = ResilienceConfig()
    # structured-event export (obs/metrics.py, DESIGN.md §15); the
    # default is disabled -- a jsonl_path or ring size turns it on
    metrics: MetricsConfig = MetricsConfig()


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Everything one detection deployment needs, as one pytree.

    `hog` is the single source of truth for the window geometry and
    numerics mode: `detector.hog` is forced to match it at construction
    (passing a non-default `detector.hog` with a default `hog` promotes
    the detector's -- whichever was explicitly set wins).
    """

    name: str = "default"
    hog: HOGConfig = PAPER_HOG
    detector: DetectorConfig = DetectorConfig()
    tracker: TrackerConfig = TrackerConfig()
    train: SVMTrainConfig = SVMTrainConfig()
    service: ServiceConfig = ServiceConfig()
    cascade: CascadeConfig = CascadeConfig()

    def __post_init__(self):
        if self.detector.hog != self.hog:
            if self.hog == PAPER_HOG:
                object.__setattr__(self, "hog", self.detector.hog)
            else:
                object.__setattr__(
                    self, "detector",
                    dataclasses.replace(self.detector, hog=self.hog))

    # -------------------------------------------------- JSON round trip
    def to_dict(self) -> Dict[str, Any]:
        """Nested plain-python dict (json.dumps-able as is)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PipelineConfig":
        """Inverse of to_dict; accepts JSON-decoded dicts (lists become
        the tuples the dataclasses expect). `from_dict(to_dict(p)) == p`."""
        return _build(cls, d)

    def to_json(self, **kw) -> str:
        kw.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_json(cls, s: str) -> "PipelineConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kw) -> "PipelineConfig":
        return dataclasses.replace(self, **kw)


def _build(cls, d: Dict[str, Any]):
    """Reconstruct a (nested) config dataclass from a plain dict. Field
    types are taken from the class defaults -- every field of the config
    tree has an instance default, so no annotation parsing is needed."""
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue
        v = d[f.name]
        if dataclasses.is_dataclass(f.default) and isinstance(v, dict):
            v = _build(type(f.default), v)
        elif isinstance(v, list):        # JSON has no tuples
            v = tuple(v)
        kwargs[f.name] = v
    return cls(**kwargs)


# ------------------------------------------------------- preset registry

_PRESETS: Dict[str, PipelineConfig] = {}


def register_preset(name: str, cfg: PipelineConfig) -> PipelineConfig:
    _PRESETS[name] = cfg
    return cfg


def presets(name: Optional[str] = None):
    """presets() -> registered names; presets(name) -> PipelineConfig."""
    if name is None:
        return tuple(sorted(_PRESETS))
    try:
        return _PRESETS[name]
    except KeyError:
        raise ValueError(
            f"unknown preset {name!r}; registered: "
            f"{', '.join(sorted(_PRESETS))}") from None


def _register_builtin() -> None:
    register_preset("default", PipelineConfig())
    register_preset("paper", PipelineConfig(
        name="paper", hog=hog_svm.CONFIG,
        detector=DetectorConfig(hog=hog_svm.CONFIG, score_threshold=0.5),
        train=hog_svm.TRAIN))
    register_preset("faithful", PipelineConfig(
        name="faithful", hog=hog_svm.FAITHFUL,
        detector=DetectorConfig(hog=hog_svm.FAITHFUL, score_threshold=0.5),
        train=hog_svm.TRAIN))
    # perf: dense-grid fused Pallas HOG (fused_hog.dense_fused_hog, bf16
    # descriptors) feeding the MXU matmul scorer with f32 accumulation;
    # batch_chunk=0 autotunes the detect_batch scan-vs-vmap schedule
    register_preset("perf", PipelineConfig(
        name="perf", hog=hog_svm.PERF,
        detector=DetectorConfig(hog=hog_svm.PERF, score_threshold=0.5,
                                backend="fused", batch_chunk=0),
        train=hog_svm.TRAIN))
    # sharded: the paper numerics on every visible device -- the frame
    # batch rides the 'data' mesh axis (core/detector.py sharded path),
    # with the per-device scan-vs-vmap schedule autotuned at first use
    register_preset("sharded", PipelineConfig(
        name="sharded", hog=hog_svm.CONFIG,
        detector=DetectorConfig(hog=hog_svm.CONFIG, score_threshold=0.5,
                                data_parallel=0, batch_chunk=0),
        train=hog_svm.TRAIN))
    # uhd: single-frame latency on big frames -- every visible device
    # tiles ONE frame's pyramid (frame_parallel=0, row-slab mode) with
    # the banded O(taps)-per-pixel resize; frames below 1280x720 keep
    # the untiled program. max_detections=0 scales top-k with the
    # window grid so 4K frames don't saturate. See DESIGN.md §11.
    register_preset("uhd", PipelineConfig(
        name="uhd", hog=hog_svm.CONFIG,
        detector=DetectorConfig(hog=hog_svm.CONFIG, score_threshold=0.5,
                                frame_parallel=0, tile_mode="slab",
                                pyramid_resize="banded",
                                frame_parallel_min_area=1280 * 720,
                                batch_chunk=0),
        train=hog_svm.TRAIN))
    # quant: the hardware paper's fixed-point datapath as a first-class
    # mode -- numerics="fixed" routes every backend through the integer
    # CORDIC mag/bin unit, int16 histograms, per-block int8 descriptors
    # and the int8 x int8 -> int32 scoring matmul (fused dense backend,
    # autotuned schedule). See DESIGN.md §12 and the BENCH "quant"
    # section for int8-vs-bf16 scoring timings.
    register_preset("quant", PipelineConfig(
        name="quant", hog=hog_svm.QUANT,
        detector=DetectorConfig(hog=hog_svm.QUANT, score_threshold=0.5,
                                backend="fused", batch_chunk=0),
        train=hog_svm.TRAIN))
    # cascade: two-stage scheduling -- the 66x34 half-resolution coarse
    # head sweeps the frame at a loose threshold and only its hit
    # neighbourhoods run the full dense chain (core/cascade.py,
    # DESIGN.md §13). session.cascade() builds the scheduler; BENCH
    # "cascade" records the retention/speedup gate.
    register_preset("cascade", PipelineConfig(
        name="cascade", hog=hog_svm.CONFIG,
        detector=DetectorConfig(hog=hog_svm.CONFIG, score_threshold=0.5),
        train=hog_svm.TRAIN,
        cascade=CascadeConfig(enabled=True)))
    # resilient: the serving-SLO deployment -- 500 ms request budgets
    # shed doomed work pre-compute, the cascade rungs back the
    # degradation ladder (full -> cascade -> coarse on overload, with
    # hysteresis), and the breaker fail-fasts admission after repeated
    # worker deaths (serve/resilience.py, DESIGN.md §14).
    register_preset("resilient", PipelineConfig(
        name="resilient", hog=hog_svm.CONFIG,
        detector=DetectorConfig(hog=hog_svm.CONFIG, score_threshold=0.5),
        train=hog_svm.TRAIN,
        cascade=CascadeConfig(enabled=True),
        service=ServiceConfig(resilience=ResilienceConfig(
            deadline_ms=500.0,
            retry=RetryPolicy(max_attempts=3, backoff_base_ms=5.0,
                              backoff_cap_ms=200.0),
            breaker_failures=5, breaker_reset_s=5.0,
            degrade_p99_ms=120.0, degrade_depth=32,
            recover_dwell=3))))


_register_builtin()
