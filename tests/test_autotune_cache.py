"""Disk-persisted autotune decisions (core/autotune_cache.py).

Unit tests of path resolution, the store/lookup round-trip (including
the JSON string-key -> int-key restoration), corruption tolerance and
fingerprint scoping, plus one end-to-end test: a probed detect_batch
schedule written by one "process" is restored from disk by the next
(memory cache cleared) without re-probing.
"""
import dataclasses
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import autotune_cache, detector
from repro.core.detector import DetectorConfig, FrameDetector

RNG = np.random.default_rng(5)
SVM = {"w": jnp.asarray(RNG.normal(size=3780).astype(np.float32) * .01),
       "b": jnp.float32(0.0)}


@pytest.fixture(autouse=True)
def _fresh_cache():
    autotune_cache._reset_for_tests()
    yield
    autotune_cache._reset_for_tests()


def test_path_resolution(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune_cache.cache_path().endswith("autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    assert autotune_cache.cache_path() is None          # disabled
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    assert autotune_cache.cache_path() == str(tmp_path / "c.json")


def test_store_lookup_roundtrip(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune_cache.store("k1", 4, {1: 9.5, 4: 3.25})
    got = autotune_cache.lookup("k1")
    assert got == {"chunk": 4, "probe_ms": {1: 9.5, 4: 3.25}}
    assert all(isinstance(c, int) for c in got["probe_ms"])  # not JSON str
    assert autotune_cache.lookup("other-key") is None
    s = autotune_cache.stats()
    assert s["probes"] == 1 and s["writes"] == 1 and s["disk_hits"] == 1
    assert s["path"] == str(path)
    # entries are scoped to the host fingerprint
    on_disk = json.loads(path.read_text())
    assert set(on_disk) == {autotune_cache.host_fingerprint()}


def test_disabled_cache_still_counts_probes(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    autotune_cache.store("k1", 1, {1: 2.0})
    assert autotune_cache.lookup("k1") is None
    s = autotune_cache.stats()
    assert s["probes"] == 1 and s["writes"] == 0 and s["path"] is None
    assert not list(tmp_path.iterdir())


def test_corrupt_file_degrades_to_probe(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    assert autotune_cache.lookup("k1") is None
    assert autotune_cache.stats()["load_errors"] == 1
    autotune_cache.store("k1", 2, {1: 5.0, 2: 1.0})     # recovers the file
    assert autotune_cache.lookup("k1")["chunk"] == 2
    json.loads(path.read_text())                        # valid again


def test_other_host_fingerprint_is_ignored(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    entry = {"some-other-host": {"k1": {"chunk": 7, "probe_ms": {"1": 1.0}}}}
    path.write_text(json.dumps(entry))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    assert autotune_cache.lookup("k1") is None
    assert autotune_cache.stats()["disk_hits"] == 0


def test_entry_key_tracks_config(monkeypatch):
    a = DetectorConfig()
    b = dataclasses.replace(a, score_threshold=0.25)
    assert autotune_cache.entry_key("K", a) != autotune_cache.entry_key("K", b)
    assert autotune_cache.entry_key("K", a) == autotune_cache.entry_key("K", a)


def test_probe_persists_and_warm_start_restores(monkeypatch, tmp_path):
    """End to end: batch_chunk=0 probes (2 candidates at B=2), writes
    the decision to disk; a cold in-memory cache then restores it from
    disk -- no probe, source=='disk', counters say so."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    # threshold unique to this test: the autotune key includes the full
    # config, and an identical tuple probed by an earlier module would
    # memory-hit here and leave nothing to persist
    cfg = DetectorConfig(score_threshold=-9.625, scales=(1.0,),
                         batch_chunk=0)
    frames = np.stack([RNG.integers(0, 256, (160, 128, 3)).astype(np.uint8)
                       for _ in range(2)])
    det = FrameDetector(SVM, cfg)
    want = [d.to_list() for d in det.detect_batch_raw(frames)]
    key = detector._autotune_key_str(
        (160, 128, 160, 128, 2, cfg, "rgb-uint8", 1, 1))
    entry = detector.autotune_report()[key]
    assert entry["source"] == "probe"
    assert set(entry["probe_ms"]) == {1, 2}
    s = autotune_cache.stats()
    assert s["probes"] == 1 and s["writes"] == 1

    # "new process": drop the in-memory decision, keep the disk file
    saved = {k: v for k, v in detector._AUTOTUNE.items()}
    detector._AUTOTUNE.clear()
    autotune_cache._reset_for_tests()
    got = [d.to_list() for d in det.detect_batch_raw(frames)]
    assert got == want
    entry2 = detector.autotune_report()[key]
    assert entry2["source"] == "disk"
    assert entry2["chunk"] == entry["chunk"]
    assert entry2["probe_ms"] == entry["probe_ms"]      # int keys restored
    s2 = autotune_cache.stats()
    assert s2["disk_hits"] == 1 and s2["probes"] == 0
    # third call: pure memory hit, disk untouched
    det.detect_batch_raw(frames)
    assert autotune_cache.stats()["memory_hits"] == 1
    detector._AUTOTUNE.update(saved)


@pytest.mark.parametrize("rgb", [True, False], ids=["rgb", "gray"])
def test_interleaved_rgb_probe_keeps_the_rgb_key(rgb):
    """An RGB batch reaches the probe as (B, h, w*3) interleaved rows
    and still keys as `rgb-uint8`, as the on-disk entries do; a gray
    batch keys as `gray-uint8`. The probe compiles the program the call
    then runs, noted with the same frame argument."""
    cfg = DetectorConfig(score_threshold=-9.5 - 0.0625 * rgb, scales=(1.0,),
                         batch_chunk=0)
    shape = (2, 160, 128, 3) if rgb else (2, 160, 128)
    frames = RNG.integers(0, 256, shape).astype(np.uint8)
    FrameDetector(SVM, cfg).detect_batch_raw(frames)
    layout = "rgb-uint8" if rgb else "gray-uint8"
    key = detector._autotune_key_str(
        (160, 128, 160, 128, 2, cfg, layout, 1, 1))
    entry = detector.autotune_report()[key]
    assert entry["source"] == "probe"
    chosen = dataclasses.replace(cfg, batch_chunk=entry["chunk"])
    fn = detector._batch_fn(160, 128, 160, 128, 2, chosen,
                            detector._donate())
    assert detector._PROGRAMS[fn]["args"][0].shape == \
        (2, 160, 128 * 3 if rgb else 128)
