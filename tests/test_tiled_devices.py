"""The tiled-equivalence cases of tests/test_tiled.py on four devices.

Those cases need several devices and skip on one, which is what a plain
test run sees. Forcing host devices must precede JAX's start, so this
test runs the file in a child process with REPRO_TEST_DEVICES=4 and
asserts that the child passed and skipped no case that four devices can
run: the only skips left are cases that ask for more than four.
"""
import os
import pathlib
import re
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
DEVICES = 4
_SKIP = re.compile(r"^SKIPPED \[\d+\] (\S+): (.*)$")
_NEEDS = re.compile(r"needs (\d+) devices")


def test_tiled_cases_pass_on_four_forced_devices():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTEST_XDIST_WORKER",
                        "PYTEST_ADDOPTS")}
    env.update(REPRO_TEST_DEVICES=str(DEVICES), JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rs", "-p",
         "no:cacheprovider", "-p", "no:randomly",
         str(ROOT / "tests" / "test_tiled.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    out = run.stdout + run.stderr
    assert run.returncode == 0, out[-6000:]
    assert re.search(r"\b\d+ passed\b", out), out[-2000:]
    for line in out.splitlines():
        m = _SKIP.match(line)
        if m:
            need = _NEEDS.search(m.group(2))
            assert need and int(need.group(1)) > DEVICES, line
