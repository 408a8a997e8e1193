"""DetectionSession -- the one host-facing entry point of the system.

The paper's co-processor has a single command interface the host CPU
drives (§VI); the repro had grown five -- `detect()`,
`FrameDetector.__call__`, `detect_batch`, `VideoDetector.process_clip`,
`DetectionService.detect_frames` -- each with its own config and result
shape. `DetectionSession` owns the SVM parameters and the compiled
detection programs once, and exposes every path behind one facade built
from one `PipelineConfig`:

    session = DetectionSession.train(presets("paper"))   # or (svm, cfg)
    session.warmup([(480, 640), (8, 480, 640)])          # compile ahead
    dets   = session.detect(frame)          # -> Detections (lazy decode)
    batch  = session.detect_batch(frames)   # -> batched Detections
    frames = session.stream(clip)           # -> tracked, per-frame
    svc    = session.serve().start()        # -> DetectionService

Compiled-program policy: programs are cached per frame-shape bucket in
the module-level lru caches of core/detector.py (shared across sessions
with equal configs -- a second session costs nothing). `warmup(shapes)`
compiles ahead of traffic, `cache_stats()` reports hits/misses/size,
`clear_cache()` evicts (process-wide; documented in DESIGN.md §8).

SVM parameters round-trip through checkpoint/manager.py
(`session.save(dir)` / `DetectionSession.load(dir, cfg)`), so CLI runs
and services skip retraining.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import PipelineConfig, presets
from repro.api.results import Detections
from repro.core import detector as _detector
from repro.core.detector import (FrameDetector, _batch_fn, _frame_program,
                                 _sharded_batch_fn, _single_fn,
                                 _tile_local_fn, _tiled_batch_fn,
                                 _tiled_single_fn, program_loads,
                                 ready_programs)
from repro.core.heads import HeadRegistry
from repro.core.hog import hog_descriptor
from repro.core.svm import SVMParams, train_svm
from repro.core.video import Tracker

ConfigLike = Union[PipelineConfig, str, None]


def _as_config(config: ConfigLike) -> PipelineConfig:
    if config is None:
        return PipelineConfig()
    if isinstance(config, str):
        return presets(config)
    return config


class DetectionSession:
    """SVM params + one PipelineConfig -> every detection path.

    Construct with trained params, or via `train` (synthetic data,
    config.train schedule) or `load` (checkpoint directory).
    """

    def __init__(self, svm: Union[SVMParams, HeadRegistry],
                 config: ConfigLike = None):
        self.config = _as_config(config)
        if isinstance(svm, HeadRegistry):
            # multi-head session: stack every public head into one
            # widened parameter block (core/heads.py); per-head
            # threshold overrides land in class_thresholds, and the
            # head names ride into every Detections as class labels
            self.registry: Optional[HeadRegistry] = svm
            stacked, names, thresholds = svm.stacked()
            det_cfg = self.config.detector
            resolved = tuple(det_cfg.score_threshold if t is None else t
                             for t in thresholds)
            det_cfg = dataclasses.replace(det_cfg,
                                          class_thresholds=resolved)
            self.svm = stacked
            self.detector = FrameDetector(stacked, det_cfg, classes=names)
        else:
            self.registry = None
            self.svm = svm
            self.detector = FrameDetector(svm, self.config.detector)
        self._class_detectors: Dict[Tuple[str, ...], FrameDetector] = {}
        self.train_losses = None       # set by train()
        self.mined_negatives = 0       # hard negatives added by train()
        self._warm: set = set()
        self._stats = {"frames": 0, "batches": 0, "clips": 0}

    # ------------------------------------------------------ construction
    @classmethod
    def train(cls, config: ConfigLike = None, n_pos: int = 1500,
              n_neg: int = 1000, seed: int = 0, data_cfg=None,
              rng: Optional[np.random.Generator] = None,
              hard_negative_rounds: int = 0, mine_scenes: int = 16
              ) -> "DetectionSession":
        """Train the SVM on synthetic pedestrian windows using the
        tree's `hog` geometry and `train` schedule. Pass `rng` to
        share a caller's stream (it advances by the window draws).

        `hard_negative_rounds` > 0 adds that many bootstrapping rounds
        (data/mining.py): each sweeps the current head over
        `mine_scenes` person-free scenes at a loose threshold and
        retrains with the firing windows as extra negatives -- the fix
        for the dense-scan domain gap (downscaled pyramid levels are
        smoother than any window-sized training negative), and what the
        cascade's retention contract is calibrated against."""
        from repro.data.mining import mine_hard_negatives
        from repro.data.synth_pedestrian import (PedestrianDataConfig,
                                                 make_windows)
        config = _as_config(config)
        if rng is None:
            rng = np.random.default_rng(seed)
        x, y = make_windows(n_pos, n_neg,
                            data_cfg or PedestrianDataConfig(), rng)
        feats = np.asarray(hog_descriptor(jnp.asarray(x), config.hog))
        labels = np.asarray(y)
        svm, losses = train_svm(jnp.asarray(feats), jnp.asarray(labels),
                                config.train)
        mined = 0
        for _ in range(int(hard_negative_rounds)):
            neg = mine_hard_negatives(svm, config.detector, mine_scenes,
                                      rng)
            if not len(neg):
                break
            mined += len(neg)
            feats = np.concatenate(
                [feats, np.asarray(hog_descriptor(jnp.asarray(neg),
                                                  config.hog))])
            labels = np.concatenate(
                [labels, np.zeros(len(neg), labels.dtype)])
            svm, losses = train_svm(jnp.asarray(feats),
                                    jnp.asarray(labels), config.train)
        session = cls(svm, config)
        session.train_losses = losses
        session.mined_negatives = mined
        return session

    @classmethod
    def load(cls, path: str, config: ConfigLike = None,
             step: Optional[int] = None) -> "DetectionSession":
        """Restore SVM params saved by `save` (checkpoint/manager.py
        layout); `step=None` takes the latest committed step. A
        directory carrying a `heads.json` manifest restores as a
        multi-head session (HeadRegistry round-trip)."""
        from repro.checkpoint.manager import CheckpointManager
        config = _as_config(config)
        if HeadRegistry.is_registry_checkpoint(path):
            return cls(HeadRegistry.load(path, step), config)
        mgr = CheckpointManager(path)
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {path}")
        skeleton = {
            "w": jax.ShapeDtypeStruct((config.hog.n_features,), jnp.float32),
            "b": jax.ShapeDtypeStruct((), jnp.float32)}
        return cls(mgr.restore(step, skeleton), config)

    def save(self, path: str, step: int = 0) -> None:
        """Persist the SVM params (atomic-commit checkpoint layout); a
        registry-backed session writes the multi-head layout (parameter
        pytree + heads.json) that `load` detects."""
        from repro.checkpoint.manager import CheckpointManager
        if self.registry is not None:
            self.registry.save(path, step)
            return
        CheckpointManager(path).save(step, self.svm)

    # ------------------------------------------------------------ facade
    def _detector_for(self, classes) -> FrameDetector:
        """The compiled-program handle scoring `classes`: the default
        stacked detector for None, else a cached per-subset handle
        (its own stacked block + thresholds; programs per bucket are
        shared process-wide via the detector's lru caches)."""
        if classes is None:
            return self.detector
        if self.registry is None:
            raise ValueError(
                "detect(classes=...) needs a HeadRegistry-backed "
                "session; this one holds plain single-head params")
        names = (classes,) if isinstance(classes, str) else tuple(classes)
        det = self._class_detectors.get(names)
        if det is None:
            stacked, names, thresholds = self.registry.stacked(names)
            det_cfg = self.config.detector
            resolved = tuple(det_cfg.score_threshold if t is None else t
                             for t in thresholds)
            det_cfg = dataclasses.replace(det_cfg,
                                          class_thresholds=resolved)
            det = FrameDetector(stacked, det_cfg, classes=names)
            self._class_detectors[names] = det
        return det

    def detect(self, image, classes=None) -> Detections:
        """One frame -> Detections (device-resident, lazy decode).
        `classes` picks a head subset on a registry-backed session (a
        name or sequence of names; None = every public head)."""
        self._stats["frames"] += 1
        return self._detector_for(classes).detect_raw(image)

    def detect_batch(self, frames, classes=None) -> Detections:
        """Stacked (B, H, W[, 3]) array or frame list -> one batched
        Detections; same one-bucket-per-call contract as the detector.
        With `config.detector.data_parallel != 1` the batch runs
        sharded, B/n_devices frames per device (pad-and-mask for
        non-divisible B; results byte-identical to single-device).
        `classes` picks a head subset on a registry-backed session."""
        self._stats["batches"] += 1
        return self._detector_for(classes).detect_batch_raw(frames)

    @property
    def data_devices(self) -> int:
        """Devices the batch axis resolves to (1 = unsharded)."""
        return self.detector.data_devices

    def stream(self, frames, batch_size: int = 8,
               tracker: Optional[Tracker] = None) -> List[Detections]:
        """Recorded clip -> per-frame TRACKED detections.

        Detection runs through the batched device path in `batch_size`
        chunks; the IoU tracker (config.tracker) associates in frame
        order, so `to_list()` entries carry track_id/hits/misses. Pass
        a Tracker to keep identities across multiple stream() calls.
        """
        self._stats["clips"] += 1
        trk = Tracker(self.config.tracker) if tracker is None else tracker
        n = len(frames)
        out: List[Detections] = []
        for i in range(0, n, max(1, batch_size)):
            chunk = [frames[j] for j in range(i, min(i + batch_size, n))]
            per_frame = (self.detector.detect_batch(chunk)
                         if len(chunk) > 1 else [self.detector(chunk[0])])
            out.extend(Detections.from_list(trk.update(d))
                       for d in per_frame)
        return out

    def cascade(self, coarse_svm: Optional[SVMParams] = None,
                rng: Optional[np.random.Generator] = None):
        """Build the two-stage CascadeDetector (core/cascade.py) over
        THIS session's fine detector: a half-resolution coarse head
        sweeps each frame at `config.cascade.coarse_threshold` and only
        its hit neighbourhoods run the dense chain. The coarse params
        come from (in order) the `coarse_svm` argument, the registry's
        auxiliary "_coarse" head, or a fresh synthetic training run
        (cached back into the registry when one is present)."""
        from repro.core.cascade import (_COARSE_NAME, CascadeDetector,
                                        coarse_detector, train_coarse_head)
        ccfg = self.config.cascade
        if coarse_svm is None:
            if self.registry is not None and _COARSE_NAME in self.registry:
                coarse_svm = self.registry.single(_COARSE_NAME)
            else:
                coarse_svm, _ = train_coarse_head(
                    self.config.hog, self.config.train, rng=rng)
                if self.registry is not None:
                    self.registry.add(_COARSE_NAME, coarse_svm,
                                      metadata={"role": "cascade-coarse"},
                                      replace=True)
        coarse = coarse_detector(coarse_svm, self.detector.cfg, ccfg)
        return CascadeDetector(self.detector, coarse, ccfg)

    def serve(self, **overrides) -> "DetectionService":
        """Build a DetectionService on THIS session's detector and
        config (service knobs from config.service; any engine kwarg can
        be overridden). Resilience knobs ride along from
        config.service.resilience, and a cascade-enabled config wires
        the session's CascadeDetector as the service's degradation
        rungs (full -> cascade -> coarse, DESIGN.md §14). Caller
        starts/stops it."""
        from repro.serve.engine import DetectionService
        sc = self.config.service
        opts = dict(batch_size=sc.window_batch,
                    cfg=self.config.hog,
                    path=self.config.detector.backend,
                    max_wait_ms=sc.max_wait_ms,
                    detector=self.config.detector,
                    frame_batch=sc.frame_batch,
                    max_pending_frames=sc.max_pending_frames,
                    resilience=sc.resilience,
                    metrics=sc.metrics)
        # an explicit detector override builds its own FrameDetector;
        # otherwise the service shares this session's handle (and with
        # it every already-compiled program). frame_detector rides in
        # opts so callers can override it like any other engine kwarg.
        opts["frame_detector"] = \
            None if "detector" in overrides else self.detector
        if self.config.cascade.enabled and "cascade" not in overrides:
            opts["cascade"] = self.cascade()
        opts.update(overrides)
        return DetectionService(self.svm, **opts)

    # --------------------------------------------- compiled-program cache
    def warmup(self, shapes: Iterable[Tuple[int, ...]]) -> Dict:
        """Compile ahead of traffic. `shapes` mixes (h, w) single-frame
        and (B, h, w) batched entries; each compiles (and runs on a
        zero frame) exactly the program live traffic of that shape
        would hit -- under `detector.data_parallel != 1` a (B, h, w)
        entry compiles the SHARDED per-bucket program (including the
        pad-and-mask variant when B does not divide the mesh), so a
        serving deployment warms the same multi-device executables its
        microbatcher will dispatch. What readying each new program cost
        is counted once (`cache_stats()["program_loads"]`). Returns
        cache_stats()."""
        for s in shapes:
            s = tuple(int(v) for v in s)
            if len(s) not in (2, 3):
                raise ValueError(
                    f"warmup shape must be (h, w) or (B, h, w), got {s}")
            zeros = np.zeros(s + (3,), np.uint8)
            with ready_programs():
                d = self.detector.detect_raw(zeros) if len(s) == 2 \
                    else self.detector.detect_batch_raw(zeros)
                d.block_until_ready()
            self._warm.add(s)
        return self.cache_stats()

    def cache_stats(self) -> Dict:
        """Hit/miss/size counters of the process-wide compiled-program
        caches plus this session's call and warmup bookkeeping. The
        "autotune" section reports how the batch-schedule decisions were
        sourced -- in-memory hit, disk-cache restore, or a live probe --
        plus the resolved cache path (core/autotune_cache.py). The
        "platform" block (repro.platform.describe()) records the
        environment -- backend, device count, x64, XLA flags -- so a
        checked-in stats dump is attributable to the host that made it.
        "program_loads" lists, for each (bucket, B) program `warmup`
        readied, its seconds (compile or persistent-cache load plus the
        first run), whether it "compiled" or "loaded", and the seconds
        of a first-use batch-schedule probe ("probe_s").
        """
        from repro import platform
        from repro.core import autotune_cache
        fi = _frame_program.cache_info()
        si = _single_fn.cache_info()
        tli = _tile_local_fn.cache_info()
        ti = _tiled_single_fn.cache_info()
        bi = _batch_fn.cache_info()
        shi = _sharded_batch_fn.cache_info()
        tbi = _tiled_batch_fn.cache_info()
        try:
            devices = self.detector.data_devices
        except ValueError:        # config names more devices than exist
            devices = None
        try:
            tiles = self.detector.frame_devices
        except ValueError:
            tiles = None
        return {
            "frame_programs": {"hits": fi.hits + si.hits + ti.hits
                               + tli.hits,
                               "misses": fi.misses + si.misses + ti.misses
                               + tli.misses,
                               "size": fi.currsize + si.currsize
                               + ti.currsize + tli.currsize,
                               "maxsize": fi.maxsize + si.maxsize
                               + ti.maxsize + tli.maxsize},
            "batch_programs": {"hits": bi.hits + shi.hits + tbi.hits,
                               "misses": bi.misses + shi.misses
                               + tbi.misses,
                               "size": bi.currsize + shi.currsize
                               + tbi.currsize,
                               "maxsize": bi.maxsize + shi.maxsize
                               + tbi.maxsize},
            "mesh": {"data_parallel": self.config.detector.data_parallel,
                     "devices": devices,
                     "frame_parallel": self.config.detector.frame_parallel,
                     "tile_devices": tiles},
            "autotune": autotune_cache.stats(),
            "program_loads": program_loads(),
            "platform": platform.describe(),
            "warmed": sorted(self._warm),
            "calls": dict(self._stats),
        }

    def clear_cache(self) -> None:
        """Evict ALL compiled detection programs (process-wide: the
        caches are shared by every session/detector in the process)."""
        _frame_program.cache_clear()
        _single_fn.cache_clear()
        _batch_fn.cache_clear()
        _sharded_batch_fn.cache_clear()
        _tile_local_fn.cache_clear()
        _tiled_single_fn.cache_clear()
        _tiled_batch_fn.cache_clear()
        _detector._PROGRAMS.clear()
        _detector._LOADS.clear()
        self._warm.clear()
