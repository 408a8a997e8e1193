"""repro.platform -- the one process-level config seam (DESIGN.md §15).

Pinned here:
  * IDEMPOTENCE: apply() re-entry is a no-op (importing the module in
    five entry points applies the env knobs once), and re-applying
    against an explicit env is safe (every mutation is merge/setdefault).
  * PRECEDENCE: an operator-set XLA_FLAGS always survives --
    force_host_devices and REPRO_* knobs merge/append, never clobber,
    and an operator-set flag of the same name wins outright.
  * RESOLUTION: autotune cache path, deterministic seed, forced-device
    parsing, describe() snapshot keys.
  * THE GREP GATE: no jax-affecting os.environ mutation anywhere in
    src/ or benchmarks/ outside platform.py itself.
"""
import os
import pathlib
import re

import pytest

from repro import platform

FORCE = "xla_force_host_platform_device_count"


# ========================================================== idempotence

def test_apply_ran_at_import():
    # conftest imports repro.platform, so by the time any test runs the
    # process-level application already happened exactly once
    assert platform._APPLIED is not None


def test_apply_reentry_is_noop():
    first = platform.apply()
    assert platform.apply() is first          # same record, no rework
    assert platform.apply() is platform.apply()


def test_apply_twice_on_explicit_env_is_stable():
    env = {"REPRO_TEST_DEVICES": "4", "REPRO_X64": "0",
           "REPRO_XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    a1 = platform.apply(env)
    flags1 = env["XLA_FLAGS"]
    a2 = platform.apply(env)                  # merge/setdefault: no growth
    assert env["XLA_FLAGS"] == flags1
    assert a1 == a2
    assert env["XLA_FLAGS"].count(FORCE) == 1
    assert env["XLA_FLAGS"].count("fast_math") == 1


def test_explicit_env_does_not_touch_process_guard():
    guard = platform._APPLIED
    platform.apply({"REPRO_TEST_DEVICES": "2"})
    assert platform._APPLIED is guard


# =========================================================== precedence

def test_user_xla_flags_survive_force():
    env = {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    n = platform.force_host_devices(8, env)
    assert n == 8
    assert "--xla_cpu_enable_fast_math=false" in env["XLA_FLAGS"]
    assert f"--{FORCE}=8" in env["XLA_FLAGS"]


def test_user_set_device_count_wins():
    env = {"XLA_FLAGS": f"--{FORCE}=2"}
    # the operator pinned 2; a code-requested 8 must NOT override it
    assert platform.force_host_devices(8, env) == 2
    assert env["XLA_FLAGS"] == f"--{FORCE}=2"


def test_repro_test_devices_merges_not_clobbers():
    env = {"XLA_FLAGS": "--xla_dump_to=/tmp/d", "REPRO_TEST_DEVICES": "4"}
    applied = platform.apply(env)
    assert applied["forced_host_devices"] == 4
    assert "--xla_dump_to=/tmp/d" in env["XLA_FLAGS"]


def test_repro_xla_flags_existing_flag_wins():
    env = {"XLA_FLAGS": "--xla_foo=user",
           "REPRO_XLA_FLAGS": "--xla_foo=repro --xla_bar=1"}
    platform.apply(env)
    assert env["XLA_FLAGS"].count("--xla_foo") == 1
    assert "--xla_foo=user" in env["XLA_FLAGS"]   # user's value kept
    assert "--xla_bar=1" in env["XLA_FLAGS"]      # new flag appended


def test_user_jax_enable_x64_wins_over_repro_x64():
    env = {"JAX_ENABLE_X64": "1", "REPRO_X64": "0"}
    applied = platform.apply(env)
    assert env["JAX_ENABLE_X64"] == "1"           # setdefault: user wins
    assert applied["x64"] is True


def test_repro_platform_pin_setdefault():
    env = {"REPRO_PLATFORM": "cpu"}
    assert platform.apply(env)["jax_platforms"] == "cpu"
    env2 = {"REPRO_PLATFORM": "cpu", "JAX_PLATFORMS": "tpu"}
    assert platform.apply(env2)["jax_platforms"] == "tpu"


# ============================================================ resolution

def test_forced_host_devices_parser():
    assert platform.forced_host_devices({"XLA_FLAGS": f"--{FORCE}=8"}) == 8
    assert platform.forced_host_devices({"XLA_FLAGS": ""}) is None
    assert platform.forced_host_devices({}) is None
    assert platform.forced_host_devices(
        {"XLA_FLAGS": f"--{FORCE}=junk"}) is None


def test_autotune_cache_path_resolution(tmp_path):
    assert platform.autotune_cache_path(
        {"REPRO_AUTOTUNE_CACHE": ""}) is None          # "" disables
    p = str(tmp_path / "a.json")
    assert platform.autotune_cache_path(
        {"REPRO_AUTOTUNE_CACHE": p}) == p
    default = platform.autotune_cache_path({})
    assert default.endswith(os.path.join(".cache", "repro",
                                         "autotune.json"))


def test_autotune_cache_module_delegates():
    # core/autotune_cache.cache_path must resolve through the seam
    from repro.core import autotune_cache
    assert autotune_cache.cache_path() == platform.autotune_cache_path()


def test_hermetic_autotune_is_setdefault():
    env = {}
    platform.hermetic_autotune(env)
    assert env["REPRO_AUTOTUNE_CACHE"] == ""
    env = {"REPRO_AUTOTUNE_CACHE": "/keep/me.json"}
    platform.hermetic_autotune(env)
    assert env["REPRO_AUTOTUNE_CACHE"] == "/keep/me.json"


def test_compile_cache_env_var_means_nothing_set_in_code():
    env = {"JAX_COMPILATION_CACHE_DIR": "/operator/cache"}
    assert platform.compile_cache_dir(env) is None
    assert "compile_cache_dir" not in platform.apply(env)
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/operator/cache"}


def test_compile_cache_unset_is_fixed_in_checkout_path():
    root = pathlib.Path(__file__).resolve().parent.parent
    first = platform.compile_cache_dir({})
    assert first == platform.compile_cache_dir({}) == str(root / ".jax_cache")
    assert platform.apply({})["compile_cache_dir"] == first
    # this process resolved through the same rule at import
    import jax
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or first
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_default_seed():
    assert platform.default_seed({}) == 0
    assert platform.default_seed({"REPRO_SEED": "42"}) == 42
    assert platform.default_seed({"REPRO_SEED": "nonsense"}) == 0


def test_describe_snapshot_keys():
    d = platform.describe()
    for key in ("backend", "device_count", "x64", "xla_flags",
                "jax_version", "forced_host_devices", "autotune_cache",
                "seed", "applied", "process_index", "machine"):
        assert key in d, key
    assert d["backend"] in ("cpu", "gpu", "tpu")
    assert d["device_count"] >= 1
    import json
    json.dumps(d)                                  # snapshot is JSON-safe


def test_is_main_single_process():
    assert platform.is_main() is True


# ============================================================= grep gate

def test_no_env_mutation_outside_platform():
    """The repo-wide invariant the refactor exists for: no jax-affecting
    `os.environ[...] =` / setdefault / update mutation in src/ or
    benchmarks/ outside platform.py (reads are fine -- interpretation
    belongs to the seam, but a read-only get cannot clobber operator
    intent)."""
    root = pathlib.Path(__file__).resolve().parent.parent
    mutation = re.compile(
        r"os\.environ\s*\[[^]]+\]\s*=|os\.environ\.setdefault|"
        r"os\.environ\.update|os\.environ\.pop")
    offenders = []
    for sub in ("src", "benchmarks"):
        for py in (root / sub).rglob("*.py"):
            if py.name == "platform.py":
                continue
            for i, line in enumerate(py.read_text().splitlines(), 1):
                if mutation.search(line):
                    offenders.append(f"{py.relative_to(root)}:{i}")
    assert not offenders, (
        "env mutation outside repro.platform: " + ", ".join(offenders))
