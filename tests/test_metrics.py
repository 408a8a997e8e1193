"""Metrics export and span suite (DESIGN.md §15, `repro.obs.metrics`,
`repro.obs.spans`).

Two families, mirroring test_resilience.py:

  * UNIT (no device): the sink zoo -- JSONL round-trip (the schema
    contract: what JsonlSink wrote, JsonlSink.read re-parses to the
    emitted dicts), ring bounds/counts, callback/tee fan-out, Emitter
    stamping + error swallowing, MetricsConfig wiring through
    PipelineConfig JSON.
  * INTEGRATION (device): the acceptance criterion from the issue --
    a chaos run with a JSONL sink emits at least one event per rung
    transition, per restart, and per deadline shed, and the stream
    stays schema-valid end to end.

Chaos fixtures reuse test_resilience.py's tiny-frame setup (160x128,
single scale, threshold -10) so no new programs compile.
"""
import dataclasses
import json
import re
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.detector import DetectorConfig
from repro.obs import spans as obs_spans
from repro.obs.metrics import (CallbackSink, Emitter, JsonlSink,
                               MetricsConfig, MetricsSink, NullSink,
                               RingSink, TeeSink, make_sink)
from repro.serve.engine import DetectionService
from repro.serve.faults import FaultInjector, FaultSpec
from repro.serve.resilience import ResilienceConfig

RNG = np.random.default_rng(11)
SVM = {"w": jnp.asarray(RNG.normal(size=3780).astype(np.float32) * .01),
       "b": jnp.float32(0.0)}
DET_CFG = DetectorConfig(score_threshold=-10.0, scales=(1.0,))

#: every event kind the engine can emit (metrics.py module docstring)
KNOWN_KINDS = {"service_start", "batch", "rung_transition",
               "deadline_shed", "worker_failure", "restart",
               "service_stop"}


def _frames(n, h=160, w=128, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (h, w, 3)).astype(np.uint8)
            for _ in range(n)]


def _service(**kw):
    kw.setdefault("detector", DET_CFG)
    kw.setdefault("frame_batch", 1)
    kw.setdefault("max_wait_ms", 1.0)
    return DetectionService(SVM, **kw)


def _assert_stamped(events):
    """Schema contract shared by every sink: stamped fields present,
    seq unique and gapless, t_ms non-negative, kind known. (File order
    is not asserted: seq is taken under the emitter lock but the write
    happens outside it, so two threads may interleave lines.)"""
    assert events, "no events emitted"
    seqs = sorted(e["seq"] for e in events)
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert all(e["t_ms"] >= 0 for e in events)
    assert {e["kind"] for e in events} <= KNOWN_KINDS


# ================================================================ unit

def test_jsonl_round_trip(tmp_path):
    """THE export contract: what went in comes back out, dict-equal."""
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path)
    em = Emitter(sink, rank0_only=False)
    sent = [("service_start", {"devices": 4, "rungs": ["full", "coarse"]}),
            ("batch", {"n": 2, "ms_per_frame": 1.5, "queue_depth": 0}),
            ("service_stop", {"frames": 2})]
    for kind, payload in sent:
        em.emit(kind, **payload)
    em.close()

    back = JsonlSink.read(path)
    assert len(back) == len(sent)
    _assert_stamped(back)
    for ev, (kind, payload) in zip(back, sent):
        assert ev["kind"] == kind
        assert {k: ev[k] for k in payload} == payload
    # and each line is independently valid JSON (tail -f contract)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_jsonl_numpy_payloads_stay_valid(tmp_path):
    path = str(tmp_path / "np.jsonl")
    sink = JsonlSink(path)
    sink.emit({"kind": "batch", "seq": 0, "t_ms": 0.0,
               "lat": np.float32(1.5), "n": np.int64(3),
               "occ": np.asarray([0.5, 1.0])})
    sink.close()
    (ev,) = JsonlSink.read(path)
    assert ev["lat"] == 1.5 and ev["n"] == 3 and ev["occ"] == [0.5, 1.0]


def test_jsonl_append_and_close_idempotent(tmp_path):
    path = str(tmp_path / "a.jsonl")
    s1 = JsonlSink(path)
    s1.emit({"kind": "batch", "seq": 0, "t_ms": 0.0})
    s1.close()
    s1.close()                                    # double close: fine
    s1.emit({"kind": "batch", "seq": 9, "t_ms": 0.0})   # after close: dropped
    s2 = JsonlSink(path)                          # append, not truncate
    s2.emit({"kind": "batch", "seq": 1, "t_ms": 0.0})
    s2.close()
    assert [e["seq"] for e in JsonlSink.read(path)] == [0, 1]


def test_ring_sink_bounds_and_counts():
    ring = RingSink(capacity=3)
    for i in range(5):
        ring.emit({"kind": "batch" if i % 2 else "restart", "seq": i})
    evs = ring.events()
    assert len(evs) == 3                          # bounded
    assert [e["seq"] for e in evs] == [2, 3, 4]   # keeps the newest
    assert ring.counts() == {"restart": 2, "batch": 1}
    assert [e["seq"] for e in ring.events(kind="batch")] == [3]


def test_callback_and_tee_fan_out():
    got = []
    ring = RingSink(8)
    tee = TeeSink([CallbackSink(got.append), ring])
    tee.emit({"kind": "batch", "seq": 0})
    tee.close()
    assert got == ring.events() == [{"kind": "batch", "seq": 0}]


def test_sinks_satisfy_protocol():
    for sink in (NullSink(), RingSink(1), CallbackSink(lambda e: None),
                 TeeSink([])):
        assert isinstance(sink, MetricsSink)


def test_emitter_stamps_and_swallows_sink_errors():
    class Boom:
        def emit(self, event):
            raise OSError("disk full")

        def close(self):
            raise OSError("still full")

    em = Emitter(Boom(), rank0_only=False)
    em.emit("batch", n=1)
    em.emit("batch", n=2)
    assert em.dropped == 2                        # serve loop never sees it
    assert "disk full" in em.last_error
    em.close()                                    # close errors swallowed too

    ring = RingSink(8)
    em = Emitter(ring, rank0_only=False)
    em.emit("batch", n=1)
    time.sleep(0.002)
    em.emit("restart", restarts=1)
    _assert_stamped(ring.events())
    assert ring.events()[1]["t_ms"] >= ring.events()[0]["t_ms"]


def test_emitter_null_sink_inactive():
    em = Emitter(NullSink(), rank0_only=False)
    assert not em.active
    em.emit("batch", n=1)                         # cheap no-op
    assert em._seq == 0


def test_emitter_thread_safe_seq():
    ring = RingSink(4096)
    em = Emitter(ring, rank0_only=False)

    def pump():
        for _ in range(200):
            em.emit("batch", n=1)

    ts = [threading.Thread(target=pump) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    seqs = sorted(e["seq"] for e in ring.events())
    assert seqs == list(range(800))               # no duplicate stamps


def test_metrics_config_enabled_and_make_sink(tmp_path):
    assert not MetricsConfig().enabled            # all-default == off
    sink, ring = make_sink(MetricsConfig())
    assert isinstance(sink, NullSink) and ring is None

    cfg = MetricsConfig(jsonl_path=str(tmp_path / "m.jsonl"), ring=16)
    assert cfg.enabled
    sink, ring = make_sink(cfg)
    assert isinstance(sink, TeeSink) and isinstance(ring, RingSink)
    sink.emit({"kind": "batch", "seq": 0, "t_ms": 0.0})
    sink.close()
    assert ring.counts() == {"batch": 1}
    assert len(JsonlSink.read(cfg.jsonl_path)) == 1

    sink, ring = make_sink(MetricsConfig(ring=8))
    assert isinstance(sink, RingSink) and sink is ring


def test_pipeline_config_metrics_round_trip(tmp_path):
    import dataclasses
    from repro.api import PipelineConfig
    mc = MetricsConfig(jsonl_path=str(tmp_path / "m.jsonl"), ring=32,
                       stage_timing=True)
    cfg = PipelineConfig()
    cfg = cfg.replace(service=dataclasses.replace(cfg.service, metrics=mc))
    back = PipelineConfig.from_json(cfg.to_json())
    assert back.service.metrics == mc
    assert back.service.metrics.enabled
    assert back == cfg


# ========================================================= integration

def test_engine_emits_lifecycle_and_batches(tmp_path):
    """Plain run: service_start .. batch* .. service_stop, in order,
    and stats()["metrics"] reconciles with the stream."""
    path = str(tmp_path / "serve.jsonl")
    svc = _service(metrics=MetricsConfig(jsonl_path=path, ring=64))
    svc.start()
    try:
        for r in svc.detect_frames(_frames(4), timeout=120):
            assert "detections" in r
    finally:
        svc.stop()

    events = JsonlSink.read(path)
    _assert_stamped(events)
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "service_start" and kinds[-1] == "service_stop"
    batches = [e for e in events if e["kind"] == "batch"]
    assert sum(b["n"] for b in batches) == 4
    for b in batches:
        assert b["ms_per_frame"] > 0
        assert b["latency_ms"]["p99"] >= 0     # rolling snapshot rides along
        assert 0 < b["occupancy"] <= 1.0
        assert isinstance(b["rung"], str)
    start = events[0]
    assert start["platform"]["device_count"] >= 1
    stop = events[-1]
    assert stop["frames"] == 4

    m = svc.stats["metrics"]
    assert m["enabled"] and m["dropped"] == 0
    assert m["emitted"] == len(events)
    assert m["recent"]["batch"] == len(batches)


def test_metrics_disabled_is_default():
    svc = _service()
    svc.start()
    try:
        svc.detect_frames(_frames(2), timeout=120)
    finally:
        svc.stop()
    assert svc.stats["metrics"] == {"enabled": False, "emitted": 0,
                                    "dropped": 0}


def test_chaos_run_emits_transition_restart_and_shed(tmp_path):
    """The issue's acceptance criterion: a chaos run with the JSONL
    sink enabled emits >= 1 event per rung transition, worker restart,
    and deadline shed -- and the stream re-parses clean."""
    path = str(tmp_path / "chaos.jsonl")
    inj = FaultInjector([
        FaultSpec("latency", at_batches=(2, 3, 4, 5), latency_ms=80.0),
        FaultSpec("kill_worker", at_batches=(8,)),
    ], seed=0)
    svc = _service(
        metrics=MetricsConfig(jsonl_path=path, ring=64),
        faults=inj,
        resilience=ResilienceConfig(degrade_p99_ms=50.0,
                                    recover_p99_ms=20.0,
                                    recover_dwell=2, latency_window=4))

    frames = _frames(14)
    # shed first: submit with an already-hopeless deadline before start
    shed_futs = [svc.submit_frame(f, deadline_ms=1.0) for f in frames[:2]]
    time.sleep(0.05)
    svc.start()
    try:
        for f in frames:
            svc.submit_frame(f).get(timeout=120)
    finally:
        svc.stop()
    for fut in shed_futs:
        assert fut.get(timeout=5).get("deadline_exceeded")

    events = JsonlSink.read(path)
    _assert_stamped(events)
    counts = {}
    for e in events:
        counts[e["kind"]] = counts.get(e["kind"], 0) + 1

    assert counts.get("deadline_shed", 0) >= 1
    assert counts.get("rung_transition", 0) >= 1
    assert counts.get("worker_failure", 0) >= 1
    assert counts.get("restart", 0) >= 1

    trans = [e for e in events if e["kind"] == "rung_transition"]
    assert any(t["direction"] == "degrade" for t in trans)
    for t in trans:
        assert t["rung_from"] != t["rung_to"]
        assert t["direction"] in ("degrade", "recover")
    shed = [e for e in events if e["kind"] == "deadline_shed"][-1]
    assert shed["shed_total"] >= 2     # one event per shed, running total
    fail = [e for e in events if e["kind"] == "worker_failure"][0]
    assert "error" in fail and "breaker" in fail
    rst = [e for e in events if e["kind"] == "restart"][0]
    assert rst["restarts"] >= 1
    stop = [e for e in events if e["kind"] == "service_stop"][0]
    assert stop["restarts"] >= 1 and stop["deadline_shed"] >= 2


@pytest.fixture
def recorder():
    """The process's span recorder, off and empty before and after."""
    rec = obs_spans.RECORDER
    rec.stop()
    rec.drain()
    yield rec
    rec.stop()
    rec.drain()


def test_stage_timing_events_opt_in(tmp_path, recorder):
    """Stage timing is opt-in, as in-memory spans: `stage_timing=True`
    (the older spelling) switches them on at the default capacity, the
    service records them while it runs and stops at `stop()`, and the
    `service_stop` event carries each span name's count, total and self
    ms. Nothing per stage goes to the sink while serving."""
    assert MetricsConfig(stage_timing=True).spans == \
        MetricsConfig.DEFAULT_SPANS
    assert MetricsConfig(spans=5, stage_timing=True).spans == 5
    path = str(tmp_path / "stage.jsonl")
    svc = _service(metrics=MetricsConfig(jsonl_path=path,
                                         stage_timing=True))
    svc.start()
    try:
        svc.detect_frames(_frames(3), timeout=120)
    finally:
        svc.stop()
    assert not recorder.on
    events = JsonlSink.read(path)
    assert {e["kind"] for e in events} <= KNOWN_KINDS
    (stop,) = [e for e in events if e["kind"] == "service_stop"]
    got = stop["spans"]
    assert got["serve.request"]["count"] == 3
    for name in ("serve.batch", "serve.gather", "serve.run",
                 "detect.dispatch", "serve.decode", "serve.answer"):
        assert got[name]["count"] >= 1, name
        assert 0 <= got[name]["self_ms"] <= got[name]["total_ms"] + 1e-6
    assert got["serve.batch"]["self_ms"] < got["serve.batch"]["total_ms"]


def test_spans_off_by_default_record_nothing(recorder):
    """Off: one shared no-op context, nothing kept, no hook installed."""
    import gc
    assert MetricsConfig().spans == 0
    a, b = recorder.span("x"), recorder.span("y", batch=True)
    assert a is b
    with a:
        recorder.add("z", 0.0, 1.0)
    gc.collect()
    assert recorder.spans() == [] and recorder.dropped == 0
    assert recorder._on_gc not in gc.callbacks
    svc = _service()
    svc.start()
    try:
        svc.detect_frames(_frames(2), timeout=120)
    finally:
        svc.stop()
    assert recorder.spans() == []


def test_spans_nest_by_thread_with_batch_and_request_ids():
    """Each thread keeps its own stack: a child's parent is the span
    open on its thread, it inherits that span's batch and request, and
    a span added from elsewhere keeps the ids it is given."""
    rec = obs_spans.SpanRecorder()
    rec.start(1000)
    barrier = threading.Barrier(2)

    def work(tag):
        with rec.span(f"{tag}.batch", batch=True):
            barrier.wait(timeout=10)
            with rec.span(f"{tag}.run"):
                with rec.span(f"{tag}.leaf"):
                    barrier.wait(timeout=10)
        with rec.span(f"{tag}.req", request=7 if tag == "a" else 8):
            with rec.span(f"{tag}.inner"):
                pass

    try:
        ts = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        [t.start() for t in ts]
        [t.join(timeout=30) for t in ts]
        assert not any(t.is_alive() for t in ts)
        rec.add("x.request", 1.0, 2.0, request=99, batch=3)
    finally:
        rec.stop()
    by = {s.name: s for s in rec.spans()}
    for tag, req in (("a", 7), ("b", 8)):
        batch, run, leaf = (by[f"{tag}.{n}"] for n in ("batch", "run",
                                                        "leaf"))
        assert batch.parent is None and batch.batch == batch.id
        assert run.parent == batch.id and run.batch == batch.id
        assert leaf.parent == run.id and leaf.batch == batch.id
        assert batch.start <= run.start <= leaf.start <= leaf.end \
            <= run.end <= batch.end
        assert by[f"{tag}.req"].parent is None
        assert by[f"{tag}.req"].batch is None
        assert by[f"{tag}.inner"].request == req
        assert by[f"{tag}.inner"].parent == by[f"{tag}.req"].id
    assert by["a.batch"].batch != by["b.batch"].batch
    x = by["x.request"]
    assert (x.request, x.batch, x.parent) == (99, 3, None)
    assert len({s.id for s in rec.spans()}) == len(rec.spans())


def test_spans_past_capacity_are_counted_as_dropped():
    rec = obs_spans.SpanRecorder()
    rec.start(3)
    try:
        for i in range(5):
            with rec.span(f"s{i}"):
                pass
    finally:
        rec.stop()
    kept, dropped = rec.drain()
    assert [s.name for s in kept] == ["s0", "s1", "s2"] and dropped == 2
    assert rec.drain() == ([], 0)


def test_span_totals_split_self_from_children_time():
    S = obs_spans.Span
    got = obs_spans.totals([S("leaf", 1.0, 2.0, 3, 2, None, 1),
                            S("run", 0.5, 2.5, 2, 1, None, 1),
                            S("batch", 0.0, 3.0, 1, None, None, 1),
                            S("leaf", 2.5, 2.75, 4, 1, None, 1)])
    assert got["batch"]["count"] == 1
    assert got["batch"]["total_ms"] == pytest.approx(3000.0)
    assert got["batch"]["self_ms"] == pytest.approx(750.0)
    assert got["run"]["self_ms"] == pytest.approx(1000.0)
    assert got["leaf"] == {"count": 2, "total_ms": pytest.approx(1250.0),
                           "self_ms": pytest.approx(1250.0)}


def test_gc_and_compile_spans_nest_under_the_open_span():
    """`host.gc` from the gc hook and `jax.compile` from JAX's compile
    event are recorded as children of the span open on the thread,
    while the recorder is on; off, both hooks are gone."""
    import gc
    rec = obs_spans.SpanRecorder()
    rec.start(100)
    try:
        with rec.span("stage", batch=True):
            gc.collect()
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    finally:
        rec.stop()
    assert rec._on_gc not in gc.callbacks
    by = {}
    for s in rec.spans():
        by.setdefault(s.name, []).append(s)
    (stage,) = by["stage"]
    for name in ("host.gc", "jax.compile"):
        assert by.get(name), name
        for s in by[name]:
            assert s.parent == stage.id and s.batch == stage.batch
            assert stage.start - 1e-3 <= s.start <= s.end <= stage.end


def test_served_batch_yields_nested_service_and_detector_spans(recorder):
    """A served batch on the CPU: `serve.batch` holds `serve.gather`,
    `serve.run` (holding `detect.stack`, `detect.upload`,
    `detect.dispatch`), `serve.decode` (holding one `detect.fetch` and
    one `detect.decode` of the batch, and a `detect.slice` a frame) and
    `serve.answer`; each request's
    `serve.request` names its own request and the batch that answered
    it."""
    svc = _service(frame_batch=3, max_wait_ms=2000.0,
                   metrics=MetricsConfig(spans=10_000))
    frames = _frames(3)
    svc.start()
    try:
        # the straggler wait (2 s) gathers all three into one batch
        futs = [svc.submit_frame(f) for f in frames]
        for f in futs:
            assert "error" not in f.get(timeout=120)
    finally:
        svc.stop()
    got = recorder.spans()
    by_id = {s.id: s for s in got}
    (batch,) = [s for s in got if s.name == "serve.batch"]
    kids = {s.name: s for s in got if s.parent == batch.id}
    assert set(kids) >= {"serve.gather", "serve.run", "serve.decode",
                         "serve.answer"}
    for name, parent in (("detect.stack", "serve.run"),
                         ("detect.upload", "serve.run"),
                         ("detect.dispatch", "serve.run"),
                         ("detect.slice", "serve.decode"),
                         ("detect.fetch", "serve.decode"),
                         ("detect.decode", "serve.decode")):
        mine = [s for s in got if s.name == name]
        assert mine, name
        for s in mine:
            assert by_id[s.parent].name == parent, name
            assert s.batch == batch.id
    assert len([s for s in got if s.name == "detect.slice"]) == 3
    # the batch is fetched to the host once and each frame sliced there
    assert len([s for s in got if s.name == "detect.fetch"]) == 1
    assert len([s for s in got if s.name == "detect.decode"]) == 1
    for s in got:
        if s.batch == batch.id and s.name != "serve.request":
            assert batch.start <= s.start <= s.end <= batch.end, s.name
    reqs = [s for s in got if s.name == "serve.request"]
    assert len(reqs) == 3 and len({s.request for s in reqs}) == 3
    assert all(s.batch == batch.id and s.start < batch.start for s in reqs)


@pytest.mark.parametrize("max_detections", [0, 4])
def test_served_batches_fetch_their_results_once(tmp_path, max_detections):
    """Over batched traffic the service copies each batch's results to
    the host once (`result_fetches == frame_batches`, also in the
    `service_stop` totals), and answers every frame as the per-frame
    `detect_raw` does, `saturated` flags included (max_detections=4
    saturates every frame)."""
    cfg = dataclasses.replace(DET_CFG, max_detections=max_detections)
    if max_detections:
        cfg = dataclasses.replace(cfg, score_threshold=-1e9)
    path = str(tmp_path / "fetch.jsonl")
    svc = _service(detector=cfg, frame_batch=3, max_wait_ms=2000.0,
                   metrics=MetricsConfig(jsonl_path=path))
    frames = _frames(6)
    svc.start()
    try:
        futs = [svc.submit_frame(f) for f in frames]
        answers = [f.get(timeout=120) for f in futs]
    finally:
        svc.stop()
    st = svc.stats
    assert st["frames"] == 6 and st["batch_fallbacks"] == 0
    assert st["frame_batches"] < 6                 # batches did form
    assert st["result_fetches"] == st["frame_batches"]
    (stop,) = [e for e in JsonlSink.read(path)
               if e["kind"] == "service_stop"]
    assert stop["result_fetches"] == st["result_fetches"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for f, a in zip(frames, answers):
            res = svc._detector.detect_raw(f)
            assert a["detections"] == res.to_list()
            assert a["saturated"] == bool(np.any(res.saturated))
            assert a["saturated"] == bool(max_detections)


# ====================================== frame program scopes and loads

SCOPE_CFG = DetectorConfig(score_threshold=-10.0, scales=(1.0, 0.8),
                           batch_chunk=2)


@pytest.fixture(scope="module")
def lowered_programs():
    """The lowered text of the single-frame and the batched program at a
    frame size where both pyramid levels hold a window."""
    from repro.core.detector import _batch_fn, _single_fn
    h, w = 200, 100
    fr = jax.ShapeDtypeStruct((h, w * 3), jnp.uint8)
    wv = jax.ShapeDtypeStruct((3780,), jnp.float32)
    bv = jax.ShapeDtypeStruct((), jnp.float32)
    single = _single_fn(h, w, h, w, SCOPE_CFG).lower(
        fr, wv, bv, jax.ShapeDtypeStruct((2,), jnp.float32))
    batched = _batch_fn(h, w, h, w, 4, SCOPE_CFG).lower(
        jax.ShapeDtypeStruct((4, h, w * 3), jnp.uint8), wv, bv,
        jax.ShapeDtypeStruct((4, 2), jnp.float32))
    names = {}
    for k, low in (("single", single), ("batched", batched)):
        # scope paths of the ops, transform wrappers (vmap(...)) removed
        names[k] = {re.sub(r"[\w.]+\(|\)", "", n) for n in re.findall(
            r'loc\("([^"]*)"', low.as_text(debug_info=True))}
    return names


@pytest.mark.parametrize("program", ["single", "batched"])
@pytest.mark.parametrize("scope", ["gray", "resize", "hog", "score/matmul",
                                   "score/collate", "select", "nms"])
def test_each_stage_scope_is_in_the_lowered_frame_program(
        lowered_programs, program, scope):
    """Every stage of the frame program carries its named scope into
    the program's metadata (the device trace's `op_name`); under the
    batch's vmap a path reads `vmap(<scope>)/...`."""
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    assert any(rx.search(n) for n in lowered_programs[program]), scope


def test_program_loads_count_each_bucket_and_batch_once():
    """warmup records what readying each (bucket, B) program cost, once:
    a shape warmed again, or a second session on the same programs,
    adds nothing; `op_scopes` reads the stages back from each
    dispatched program's compiled text."""
    from repro.api import DetectionSession, PipelineConfig
    from repro.core import detector
    cfg = PipelineConfig()
    cfg = cfg.replace(detector=dataclasses.replace(
        SCOPE_CFG, score_threshold=-7.5))
    session = DetectionSession(SVM, cfg)
    session.clear_cache()
    stats = session.warmup([(200, 100), (2, 200, 100), (200, 100)])
    loads = stats["program_loads"]
    bucket = session.detector.bucket_for(np.zeros((200, 100, 3)))
    assert [(r["bucket"], r["batch"]) for r in loads] == \
        [(bucket, 1), (bucket, 2)]
    for r in loads:
        assert r["seconds"] > 0 and r["source"] in (
            "compiled", "loaded", "in memory")
        assert r["compiles"] >= 0 and r["cache_hits"] >= 0
        assert r["probe_s"] == 0
    DetectionSession(SVM, cfg).warmup([(2, 200, 100)])
    assert detector.program_loads() == loads
    for batch in (1, 2):
        scopes = detector.op_scopes(batch)
        assert scopes and any("/nms/" in v or "(nms)" in v
                              for v in scopes.values())
    session.clear_cache()
    assert detector.program_loads() == []


def test_hlo_op_names_fill_what_the_compiler_left_without_metadata():
    """A fusion the compiler built takes an op_name from the computation
    it calls; a layout copy or tuple access takes its operand's."""
    from repro.core.detector import hlo_op_names
    text = """HloModule jit_fn

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(fn)/vmap(nms)/while/body/add"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="frame"}
  %fusion.7 = f32[4]{0:T(8,128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %custom-call.3 = (f32[4]{0}, f32[4]{0}) custom-call(%fusion.7), custom_call_target="k", metadata={op_name="jit(fn)/hog/jit(dense_fused_hog)"}
  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%custom-call.3), index=0
  ROOT %copy.2 = f32[4]{0:T(8,128)} copy(%get-tuple-element.5)
}
"""
    got = hlo_op_names(text)
    nms = "jit(fn)/vmap(nms)/while/body/add"
    hog = "jit(fn)/hog/jit(dense_fused_hog)"
    assert got == {"add.1": nms, "Arg_0.1": "frame", "fusion.7": nms,
                   "custom-call.3": hog, "get-tuple-element.5": hog,
                   "copy.2": hog}
