"""The one traffic generator: every mix is a data file it reads.

Two kinds of mix (`"kind"` in chipbench/traffic/<mix>.json):

  open    `cameras` streams, each periodic at `fps`, with a phase drawn
          from the seed and up to `jitter_ms` of arrival jitter per
          frame. Frames are sent when due, whatever the backlog; each is
          timed from when it was due until its answer arrives.
  closed  `streams` streams with `in_flight` frames outstanding in all;
          each answer sends the next frame of the same stream.

Each stream plays its own seeded clip (`clip.frames` frames with
`clip.people` pedestrians, cycled) at the configuration's frame size.
Every seed gives the same number of frames at the same sizes; the seed
moves the phases, the jitter and the pictures. A mix also names the
batch sizes its traffic can form (`batch_sizes`, all warmed in set-up)
and how many answers the check samples (`check_frames`).

Each record keeps when its frame began to be submitted and when the
collector began to wait for its answer, so that a trace's idle gaps can
be labelled by what the benchmark was doing.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.clips import make_clip

#: how long past the window's close an answer is still waited for
ANSWER_GRACE_S = 60.0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def open_schedule(mix: dict, seed: int, seconds: float
                  ) -> List[Tuple[float, int, int]]:
    """(due seconds from the window's open, camera, frame number) of
    every frame due in the window, in due order. Each camera sends
    exactly fps * seconds frames: phases and jitter together stay
    inside one frame period."""
    rng = _rng(seed, 1)
    period = 1.0 / float(mix["fps"])
    jitter = float(mix.get("jitter_ms", 0.0)) / 1e3
    n = int(round(seconds * float(mix["fps"])))
    out = []
    for cam in range(int(mix["cameras"])):
        phase = rng.uniform(0.0, period - jitter)
        dues = phase + np.arange(n) * period + rng.uniform(0.0, jitter, n)
        out += [(float(d), cam, k) for k, d in enumerate(dues)]
    out.sort()
    return out


def streams(mix: dict) -> int:
    return int(mix["cameras"] if mix["kind"] == "open" else mix["streams"])


def clips(mix: dict, h: int, w: int, seed: int) -> List[np.ndarray]:
    """One (frames, h, w, 3) uint8 clip per stream."""
    rng = _rng(seed, 2)
    c = mix["clip"]
    return [make_clip(rng, h, w, int(c["frames"]), int(c["people"]))
            for _ in range(streams(mix))]


def check_sample(n_answered: int, k: int, seed: int) -> np.ndarray:
    """Indices of the answered frames the reference checks, drawn from
    the seed."""
    k = min(int(k), n_answered)
    return np.sort(_rng(seed, 3).choice(n_answered, size=k, replace=False))


@dataclasses.dataclass
class Record:
    stream: int
    frame: int                    # frame number in the stream
    due: float                    # absolute perf_counter time it was due
    sent: float = float("nan")
    done: float = float("nan")
    payload: Optional[dict] = None
    error: Optional[str] = None
    ready_on_arrival: bool = False
    submitting: float = float("nan")  # when its submit call began
    waiting: float = float("nan")     # when the collector began to wait

    @property
    def ok(self) -> bool:
        return self.error is None and self.payload is not None \
            and "error" not in self.payload


def _get(fut, rec: Record, until: float) -> None:
    rec.ready_on_arrival = fut.qsize() > 0
    rec.waiting = time.perf_counter()
    try:
        rec.payload = fut.get(timeout=max(0.0, until - rec.waiting))
    except queue.Empty:
        rec.error = "no answer"
    rec.done = time.perf_counter()


def _submit(submit: Callable, frame: np.ndarray, rec: Record):
    """The frame's future, or None where the service refused it (it
    then counts as failed)."""
    rec.submitting = time.perf_counter()
    try:
        fut = submit(frame)
    except Exception as e:
        fut = None
        rec.error = f"{type(e).__name__}: {e}"
    rec.sent = time.perf_counter()
    return fut


def spans(records: Sequence[Record]) -> List[Tuple[str, float, float]]:
    """(name, start, end) on the host's perf_counter clock of what the
    benchmark was doing: submitting each frame, waiting for each
    answer."""
    out = [("chipbench.submit", r.submitting, r.sent) for r in records
           if r.submitting == r.submitting]
    return out + [("chipbench.wait", r.waiting, r.done) for r in records
                  if r.waiting == r.waiting]


def run_open(submit: Callable, clip_frames: Sequence[np.ndarray],
             schedule, t_open: float, seconds: float) -> List[Record]:
    """Send every scheduled frame when it is due, from a generator
    thread; collect the answers in order on this thread."""
    recs = [Record(cam, k, t_open + due) for due, cam, k in schedule]
    sent: "queue.Queue" = queue.Queue()

    def generate():
        for i, r in enumerate(recs):
            wait = r.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            clip = clip_frames[r.stream]
            sent.put((i, _submit(submit, clip[r.frame % len(clip)], r)))

    gen = threading.Thread(target=generate, name="chipbench-generator",
                           daemon=True)
    gen.start()
    until = t_open + seconds + ANSWER_GRACE_S
    try:
        for _ in recs:
            i, fut = sent.get()
            if fut is None:
                recs[i].done = time.perf_counter()
                continue
            _get(fut, recs[i], until)
    finally:
        gen.join()
    return recs


def run_closed(submit: Callable, clip_frames: Sequence[np.ndarray],
               in_flight: int, t_open: float, seconds: float
               ) -> List[Record]:
    """Keep `in_flight` frames outstanding over the streams; each answer
    sends that stream's next frame while the window is open. Returns
    every record, answered in the window or after it."""
    t_close = t_open + seconds
    until = t_close + ANSWER_GRACE_S
    pending: "collections.deque" = collections.deque()
    next_frame = [0] * len(clip_frames)
    recs: List[Record] = []

    def send(stream: int, due: float) -> None:
        clip = clip_frames[stream]
        r = Record(stream, next_frame[stream], due)
        next_frame[stream] += 1
        fut = _submit(submit, clip[r.frame % len(clip)], r)
        recs.append(r)
        pending.append((r, fut))

    wait = t_open - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    for i in range(int(in_flight)):
        send(i % len(clip_frames), t_open)
    while pending:
        r, fut = pending.popleft()
        if fut is None:
            r.done = time.perf_counter()
        else:
            _get(fut, r, until)
        if r.done < t_close:
            send(r.stream, r.done)
    return recs
