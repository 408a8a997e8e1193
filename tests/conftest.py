"""Test session config.

All process-level environment handling lives in `repro.platform`
(DESIGN.md §15): importing it applies the REPRO_* knobs exactly once,
before jax initializes -- conftest import time is safe. In particular
REPRO_TEST_DEVICES=N forces N host devices (for the sharded /
tiled-UHD suites); default test runs see 1 device.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import platform  # noqa: E402  (applies REPRO_* at import)

# hermetic autotune: an empty path disables the DISK cache (a stale
# ~/.cache entry from a previous run would short-circuit the probe the
# autotune tests assert on); tests of the disk cache itself monkeypatch
# this to a tmp file. In-memory autotune behavior is unchanged.
platform.hermetic_autotune()

# hermetic compiles: the persistent compile cache serves the program's
# own runs; tests compile fresh and write nothing into the checkout
import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
