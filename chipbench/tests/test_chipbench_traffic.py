"""The traffic generator: fixed by the seed, the same work for every
seed, and frames of the configured size."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import cells, traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return cells.traffic(name)


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_open_schedule_is_fixed_by_the_seed(seed):
    mix = _mix("cams")
    a = traffic.open_schedule(mix, seed, 2.0)
    b = traffic.open_schedule(mix, seed, 2.0)
    assert a == b
    assert a != traffic.open_schedule(mix, seed + 1, 2.0)


@pytest.mark.parametrize("name", ["cams", "single_cam"])
def test_every_seed_sends_the_same_frames_per_camera(name):
    mix = _mix(name)
    seconds = 3.0
    for seed in (1, 2, BIG_SEED):
        sched = traffic.open_schedule(mix, seed, seconds)
        assert len(sched) == mix["cameras"] * mix["fps"] * seconds
        assert all(0.0 <= due < seconds for due, _, _ in sched)
        assert [d for d, _, _ in sched] == sorted(d for d, _, _ in sched)
        for cam in range(mix["cameras"]):
            dues = [d for d, c, _ in sched if c == cam]
            gaps = np.diff(dues)
            period = 1.0 / mix["fps"]
            jitter = mix.get("jitter_ms", 0.0) / 1e3
            assert np.all(gaps > period - jitter - 1e-9)
            assert np.all(gaps < period + jitter + 1e-9)


def test_clips_are_fixed_by_the_seed_and_sized_by_the_config():
    mix = dict(_mix("archive"), streams=3, clip={"frames": 2, "people": 2})
    a = traffic.clips(mix, 160, 200, BIG_SEED)
    b = traffic.clips(mix, 160, 200, BIG_SEED)
    c = traffic.clips(mix, 160, 200, BIG_SEED + 1)
    assert len(a) == 3
    assert all(x.shape == (2, 160, 200, 3) and x.dtype == np.uint8 for x in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_check_sample_is_fixed_by_the_seed():
    s = traffic.check_sample(100, 8, BIG_SEED)
    assert np.array_equal(s, traffic.check_sample(100, 8, BIG_SEED))
    assert len(set(s.tolist())) == 8 and s.max() < 100
    assert len(traffic.check_sample(3, 8, 1)) == 3


def test_closed_loop_keeps_the_frames_in_flight():
    import queue
    import threading

    answered = []

    def submit(frame):
        fut = queue.Queue(maxsize=1)
        threading.Timer(0.002, lambda: (answered.append(1),
                                        fut.put({"detections": []}))).start()
        return fut

    clip_frames = [np.zeros((2, 4, 4, 3), np.uint8)] * 4
    t0 = __import__("time").perf_counter()
    recs = traffic.run_closed(submit, clip_frames, 4, t0, 0.2)
    assert recs and all(r.ok for r in recs)
    # each stream's frames go out in order, one outstanding at a time
    for s in range(4):
        mine = [r for r in recs if r.stream == s]
        assert [r.frame for r in mine] == list(range(len(mine)))
        assert all(b.sent >= a.done for a, b in zip(mine, mine[1:]))


def test_records_keep_the_host_spans_that_label_idle_gaps():
    """Every frame leaves a submit span and a collector-wait span on the
    host clock; a refused frame leaves no wait."""
    import queue
    import time

    n = [0]

    def submit(frame):
        n[0] += 1
        if n[0] == 3:
            raise RuntimeError("refused")
        fut = queue.Queue(maxsize=1)
        fut.put({"detections": []})
        return fut

    clip_frames = [np.zeros((2, 4, 4, 3), np.uint8)]
    sched = [(0.001 * i, 0, i) for i in range(5)]
    recs = traffic.run_open(submit, clip_frames, sched, time.perf_counter(),
                            0.01)
    spans = traffic.spans(recs)
    assert sum(name == "chipbench.submit" for name, _, _ in spans) == 5
    assert sum(name == "chipbench.wait" for name, _, _ in spans) == 4
    assert all(a <= b for _, a, b in spans)
    assert not recs[2].ok and all(r.ok for i, r in enumerate(recs) if i != 2)
