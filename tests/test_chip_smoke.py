"""chip_smoke.py's control flow on the CPU, at tiny frame sizes with
interpreted kernels. The script itself runs only on a TPU; these tests
call its phase functions directly and check that main() refuses a host
without one."""
import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SIZES = ((192, 128), (160, 96))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def svm():
    rng = np.random.default_rng(42)
    return {"w": jnp.asarray((rng.normal(size=3780) * 0.02)
                             .astype(np.float32)),
            "b": jnp.float32(0.0)}


def _tiny(cfg):
    return cfg.replace(detector=dataclasses.replace(
        cfg.detector, score_threshold=0.0, scales=(1.0,)))


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_one_chip_phases_pass_with_a_tpu_kernel(smoke, svm, monkeypatch,
                                                capsys):
    """Every phase answers every frame, batches, and agrees with its ref
    twin; the compiled-kernel check is stood in for, since interpreted
    kernels compile to no tpu_custom_call."""
    monkeypatch.setattr(smoke, "frame_program_text",
                        lambda *a: "... tpu_custom_call ...")
    frames = smoke.make_frames(0, SIZES, 2)
    assert smoke.one_chip_phases(svm, frames, timeout=300.0,
                                 configure=_tiny)
    out = capsys.readouterr().out
    for phase in ("c:perf-ref", "a:perf", "c:quant-ref", "b:quant"):
        assert f"phase {phase}: " in out
    assert "FAIL" not in out


def test_phase_fails_without_a_tpu_kernel(smoke, svm, capsys):
    """On the CPU the Pallas kernels are interpreted: the compiled frame
    program has no tpu_custom_call, and the phase must say so."""
    frames = smoke.make_frames(1, SIZES[1:], 2)
    rep = smoke.run_phase("a:perf", _tiny(smoke.presets("perf")), svm,
                          frames, timeout=300.0)
    assert rep["tpu_custom_call"] is False and rep["ok"] is False
    assert rep["errors"] == 0 and rep["batch_fallbacks"] == 0
    assert "no tpu_custom_call" in capsys.readouterr().out


def test_phase_fails_on_a_batch_fallback_and_says_why(smoke, svm,
                                                     monkeypatch, capsys):
    """A batched program that raises is contained by the service (every
    frame still answered, one at a time), but the phase fails and names
    the error the batched program raised."""
    from repro.core.detector import FrameDetector

    def boom(self, frames, *a, **k):
        raise RuntimeError("batched program refused")

    monkeypatch.setattr(FrameDetector, "detect_batch_raw", boom)
    frames = smoke.make_frames(2, SIZES[1:], 2)
    cfg = _tiny(smoke.presets("perf"))
    cfg = cfg.replace(detector=dataclasses.replace(cfg.detector,
                                                   backend="ref"))
    rep = smoke.run_phase("c:perf-ref", cfg, svm, frames, timeout=300.0)
    assert rep["errors"] == 0 and rep["answered"] == 2 * len(frames)
    assert rep["batch_fallbacks"] == 2 and rep["ok"] is False
    assert "RuntimeError: batched program refused" in capsys.readouterr().out


MESH_REHEARSAL = """
import dataclasses, sys
import jax.numpy as jnp, numpy as np
sys.path.insert(0, {root!r})
import chip_smoke as s
rng = np.random.default_rng(42)
svm = {{"w": jnp.asarray((rng.normal(size=3780) * 0.02).astype(np.float32)),
        "b": jnp.float32(0.0)}}
tiny = lambda cfg: cfg.replace(detector=dataclasses.replace(
    cfg.detector, score_threshold=0.0, scales=(1.0,)))
sys.exit(0 if s.mesh_phases(svm, 0, 4, uhd=(736, 1280), n_uhd=1,
                            batch_hw=(192, 128), batch=8,
                            configure=tiny) else 1)
"""


def test_mesh_phases_on_four_host_devices():
    """The --chips 4 path on four forced CPU devices. Forcing host
    devices must precede jax init, so it runs in a child process; the
    child's XLA_FLAGS are its own so an inherited device count cannot
    override the four."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(REPRO_TEST_DEVICES="4", JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    run = subprocess.run(
        [sys.executable, "-c", MESH_REHEARSAL.format(root=str(ROOT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout + run.stderr[-4000:]
    for phase in ("uhd-tiled-vs-untiled", "sharded-vs-single"):
        line = next(ln for ln in run.stdout.splitlines()
                    if ln.startswith(f"phase {phase}: "))
        rep = json.loads(line.split(": ", 1)[1])
        assert rep["ok"] and rep["output_devices"] == [0, 1, 2, 3]
        # host devices run one XLA:CPU program per shard: the CPU tests'
        # byte identity holds here
        assert rep["byte_identical"]


def test_boxes_agree_criterion(smoke):
    d = {"box": (10.0, 20.0, 140.0, 86.0), "score": 0.9}
    near = dict(d, box=(10.5, 20.0, 140.5, 86.0), score=0.93)
    assert smoke.boxes_agree([[d]], [[near]], 0.5) == []
    moved = dict(d, box=(13.0, 20.0, 143.0, 86.0))
    assert len(smoke.boxes_agree([[d]], [[moved]], 0.5)) == 2
    assert len(smoke.boxes_agree([[d]], [[dict(d, score=0.8)]], 0.5)) == 2
    weak = dict(d, score=0.52)           # inside the threshold margin
    assert smoke.boxes_agree([[weak]], [[]], 0.5) == []
