"""Typed detection results -- device-resident until the host asks.

The legacy entry points each returned ad-hoc lists of dicts, decoded
eagerly on every call (one host sync per frame even when the caller only
wanted a count or wanted to stack results). `Detections` is the one
result type of the api layer:

  * holds the RAW device outputs of the compiled detection program --
    top-k `scores`, box-table `index`, NMS `keep` mask, and the
    threshold-candidate count `n_valid` -- plus the program's static
    host-side decode tables (pure geometry, numpy),
  * is a registered jax pytree, so batched results ride through
    jit/vmap/scan untouched,
  * decodes LAZILY: nothing syncs to host until `.to_list()` /
    `.boxes` / `len()` is called, and the decode is cached,
  * `.to_list()` reproduces the legacy dict contract byte for byte
    (`{"box": (y0, x0, y1, x1), "score", "scale"}`, descending score),
  * `.saturated` answers programmatically what used to be only a
    RuntimeWarning: did more candidates clear the threshold than the
    program's top-k could hold? (per-frame bool array on batches),
  * a leading batch axis makes a batch-of-frames result: `d.frame(i)`
    slices one frame out, `Detections.stack([...])` goes the other way,
  * `Detections.from_list(dicts)` wraps already-host results (the
    tracking path) so `stream()` returns the same type; extra keys such
    as `track_id` pass through `.to_list()` unchanged (they do not
    survive pytree flattening, which keeps only the device arrays).

Multi-class results (stacked-head scoring, DESIGN.md §13) carry a CLASS
axis ahead of the top-k axis -- (K, k) per frame, (B, K, k) per batch --
plus a static tuple of class names as pytree aux data. Decoding runs the
per-class slots independently (each class had its own device NMS) and
merges by descending score; every dict gains `class_id` (head index) and
`label`. `for_class()` slices one class back out as a plain single-head
result.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.detector import DecodeTables
from repro.obs import spans


class Detections:
    """Results of one detection call: a single frame (1-D top-k axis) or
    a stacked batch of frames (leading batch axis), optionally with a
    class axis between the two (see module docstring; `classes` names
    the heads). Construct via the session/detector, `from_list`, or
    `stack` -- the raw constructor mirrors the compiled program's
    outputs."""

    def __init__(self, scores, index, keep, n_valid, tables,
                 _lists: Optional[list] = None,
                 classes: Optional[Tuple[str, ...]] = None):
        self._scores = scores          # (..., K) f32, top-k order, -inf pad
        self._index = index            # (..., K) i32 rows into tables.boxes
        self._keep = keep              # (..., K) bool NMS keep mask
        self._n_valid = n_valid        # (...,)   i32 threshold candidates
        self._tables = tables          # static: .boxes (N,4), .scales (N,), .k
        self._lists = _lists           # cached host decode
        self._classes = tuple(classes) if classes is not None else None

    # ------------------------------------------------------ constructors
    @classmethod
    def empty(cls, tables, classes=None) -> "Detections":
        """Single-frame empty result (frame smaller than one window)."""
        if classes is not None:
            nc = len(classes)
            return cls(np.zeros((nc, 0), np.float32),
                       np.zeros((nc, 0), np.int32), np.zeros((nc, 0), bool),
                       np.zeros((nc,), np.int32), tables, _lists=[[]],
                       classes=classes)
        return cls(np.zeros((0,), np.float32), np.zeros((0,), np.int32),
                   np.zeros((0,), bool), 0, tables, _lists=[[]])

    @classmethod
    def empty_batch(cls, tables, n: int, classes=None) -> "Detections":
        """Batched empty result: n frames, zero candidate slots each."""
        lists = [[] for _ in range(n)]
        if classes is not None:
            nc = len(classes)
            return cls(np.zeros((n, nc, 0), np.float32),
                       np.zeros((n, nc, 0), np.int32),
                       np.zeros((n, nc, 0), bool),
                       np.zeros((n, nc), np.int32), tables, _lists=lists,
                       classes=classes)
        return cls(np.zeros((n, 0), np.float32), np.zeros((n, 0), np.int32),
                   np.zeros((n, 0), bool), np.zeros((n,), np.int32), tables,
                   _lists=lists)

    @classmethod
    def from_list(cls, dets: Sequence[Dict[str, Any]]) -> "Detections":
        """Wrap host-side detection dicts (e.g. tracker output). Extra
        keys (track_id, class_id, hits, ...) are preserved by
        to_list()."""
        dets = list(dets)
        boxes = np.asarray([d["box"] for d in dets],
                           np.float32).reshape(-1, 4)
        scores = np.asarray([d["score"] for d in dets], np.float32)
        scales = np.asarray([d.get("scale", 1.0) for d in dets], np.float32)
        k = len(dets)
        tables = DecodeTables(boxes, scales, k)
        return cls(scores, np.arange(k, dtype=np.int32),
                   np.ones((k,), bool), k, tables, _lists=[dets])

    @classmethod
    def stack(cls, dets: Sequence["Detections"]) -> "Detections":
        """Stack single-frame results that share decode tables into one
        batched result (the inverse of .frame(i))."""
        dets = list(dets)
        if not dets:
            raise ValueError("stack() needs at least one Detections")
        if any(d.batched for d in dets):
            raise ValueError("stack() takes single-frame Detections")
        t0 = dets[0]._tables
        c0 = dets[0]._classes
        for d in dets[1:]:
            same = d._tables is t0 or (
                d._tables.k == t0.k
                and np.array_equal(d._tables.boxes, t0.boxes)
                and np.array_equal(d._tables.scales, t0.scales))
            if not same:
                raise ValueError("stack() needs results from the same "
                                 "compiled program (same decode tables)")
            if d._classes != c0:
                raise ValueError("stack() needs results with the same "
                                 "class names")
        nv = [np.asarray(d._n_valid, np.int32) for d in dets] \
            if c0 is not None else \
            [np.int32(int(d._n_valid)) for d in dets]
        return cls(np.stack([np.asarray(d._scores) for d in dets]),
                   np.stack([np.asarray(d._index) for d in dets]),
                   np.stack([np.asarray(d._keep) for d in dets]),
                   np.stack(nv), t0, classes=c0)

    # -------------------------------------------------------- structure
    @property
    def classes(self) -> Optional[Tuple[str, ...]]:
        """Head names on a multi-class result, None on single-head."""
        return self._classes

    @property
    def batched(self) -> bool:
        return np.ndim(self._scores) == (3 if self._classes else 2)

    @property
    def batch_size(self) -> int:
        if not self.batched:
            raise ValueError("single-frame Detections has no batch axis")
        return int(np.shape(self._scores)[0])

    def frame(self, i: int) -> "Detections":
        """Slice one frame out of a batched result (no host sync; on the
        device each slice is a small program of its own). Span:
        `detect.slice`."""
        if not self.batched:
            raise ValueError("frame() on a single-frame Detections")
        lists = None if self._lists is None else [self._lists[i]]
        with spans.span("detect.slice"):
            return Detections(self._scores[i], self._index[i],
                              self._keep[i], self._n_valid[i], self._tables,
                              _lists=lists, classes=self._classes)

    def for_class(self, c) -> "Detections":
        """Slice one head (by name or index) out of a multi-class
        result, as a plain single-head Detections."""
        if self._classes is None:
            raise ValueError("for_class() on a single-head Detections")
        k = self._classes.index(c) if isinstance(c, str) else int(c)
        sl = (slice(None), k) if self.batched else k
        nv = np.asarray(self._n_valid)[sl]
        return Detections(self._scores[sl], self._index[sl], self._keep[sl],
                          nv if self.batched else int(nv), self._tables)

    def block_until_ready(self) -> "Detections":
        """Wait for the device computation backing this result."""
        jax.block_until_ready((self._scores, self._index,
                               self._keep, self._n_valid))
        return self

    # ----------------------------------------------------------- decode
    @property
    def saturated(self):
        """True when more candidates cleared the score threshold than
        the program's top-k (`max_detections`) could hold -- the tail
        was dropped BEFORE NMS. bool for a frame, (B,) array per batch;
        with a class axis the array keeps it ((K,) / (B, K)), one flag
        per head."""
        with spans.span("detect.fetch"):
            n_valid = np.asarray(self._n_valid)
        if self.batched or self._classes is not None:
            return n_valid > self._tables.k
        return bool(int(n_valid) > self._tables.k)

    def _decode_slots(self, top, idx, kp, n_valid, label=None) -> List[dict]:
        n_valid = int(n_valid)
        if n_valid > self._tables.k:
            who = f" (head '{label}')" if label is not None else ""
            warnings.warn(
                f"{n_valid} detection candidates cleared the "
                f"threshold but max_detections={self._tables.k}{who}; the "
                f"lowest-scoring {n_valid - self._tables.k} were "
                f"dropped before NMS (lowest kept score {top[-1]:.3f})",
                RuntimeWarning, stacklevel=5)
        kept = np.flatnonzero(kp & np.isfinite(top))
        boxes = self._tables.boxes[idx[kept]]
        scales = self._tables.scales[idx[kept]]
        return [{"box": tuple(float(v) for v in boxes[r]),
                 "score": float(top[kept[r]]),
                 "scale": float(scales[r])}
                for r in range(len(kept))]

    def _decode_frame(self, scores, index, keep, n_valid) -> List[dict]:
        top = np.asarray(scores)
        idx = np.asarray(index)
        kp = np.asarray(keep)
        if self._classes is None:
            return self._decode_slots(top, idx, kp, n_valid)
        # class axis: each head's slots decode independently (each had
        # its own device NMS), then merge by descending score -- the
        # stable sort keeps head order on ties
        merged: List[dict] = []
        nv = np.asarray(n_valid)
        for ci, name in enumerate(self._classes):
            for d in self._decode_slots(top[ci], idx[ci], kp[ci], nv[ci],
                                        label=name):
                d["class_id"] = ci
                d["label"] = name
                merged.append(d)
        merged.sort(key=lambda d: -d["score"])
        return merged

    def _decoded(self) -> list:
        """The host decode, cached. Spans: `detect.fetch`, the
        device-to-host copies (which wait for the device), and
        `detect.decode`, the Python decode."""
        if self._lists is None:
            with spans.span("detect.fetch"):
                top = np.asarray(self._scores)
                idx = np.asarray(self._index)
                kp = np.asarray(self._keep)
                nv = np.asarray(self._n_valid)
            with spans.span("detect.decode"):
                if self.batched:
                    self._lists = [self._decode_frame(top[i], idx[i], kp[i],
                                                      nv[i])
                                   for i in range(top.shape[0])]
                else:
                    self._lists = [self._decode_frame(top, idx, kp, nv)]
        return self._lists

    def to_list(self):
        """The legacy host contract: list of detection dicts for a
        frame, list of per-frame lists for a batch. Multi-class dicts
        additionally carry `class_id` and `label`."""
        lists = self._decoded()
        return lists if self.batched else lists[0]

    # ---------------------------------------------- kept-array accessors
    def _kept(self) -> List[dict]:
        if self.batched:
            raise ValueError("array accessors are per-frame; use "
                             ".frame(i) or .to_list() on a batch")
        return self._decoded()[0]

    @property
    def boxes(self) -> np.ndarray:
        """(M, 4) kept boxes as (y0, x0, y1, x1), descending score."""
        return np.asarray([d["box"] for d in self._kept()],
                          np.float32).reshape(-1, 4)

    @property
    def scores(self) -> np.ndarray:
        return np.asarray([d["score"] for d in self._kept()], np.float32)

    @property
    def scales(self) -> np.ndarray:
        return np.asarray([d["scale"] for d in self._kept()], np.float32)

    @property
    def class_ids(self) -> np.ndarray:
        """(M,) head index per kept detection (zeros on single-head)."""
        return np.asarray([d.get("class_id", 0) for d in self._kept()],
                          np.int32)

    def __len__(self) -> int:
        """Batch: number of frames. Single frame: kept detections."""
        return self.batch_size if self.batched else len(self._kept())

    def __iter__(self) -> Iterator:
        """Batch: per-frame Detections. Single frame: detection dicts."""
        if self.batched:
            return (self.frame(i) for i in range(self.batch_size))
        return iter(self._kept())

    def __repr__(self) -> str:
        cl = f", classes={len(self._classes)}" if self._classes else ""
        if self.batched:
            return (f"Detections(batch={self.batch_size}, "
                    f"k={self._tables.k}{cl})")
        if self._lists is not None:
            return f"Detections(n={len(self._lists[0])}, decoded{cl})"
        return f"Detections(k={self._tables.k}, device-resident{cl})"


def _flatten(d: Detections):
    return ((d._scores, d._index, d._keep, d._n_valid),
            (d._tables, d._classes))


def _unflatten(aux, children) -> Detections:
    return Detections(*children, aux[0], classes=aux[1])


jax.tree_util.register_pytree_node(Detections, _flatten, _unflatten)
