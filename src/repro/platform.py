"""The one owner of process-level configuration (DESIGN.md §15).

Before this module, five entry points each mutated `XLA_FLAGS` / env
their own way: `tests/conftest.py` appended the forced-host-device
flag, a bench script carried its own self-forcing block, a launcher
*overwrote* `XLA_FLAGS` outright (clobbering any operator-set flags),
and CI lanes exported ad-hoc variables. Every one
of those is a pre-jax-init footgun: jax reads `XLA_FLAGS` exactly once,
at first backend initialization, so a mutation that lands late is
silently ignored and a clobber silently discards operator intent.

This module is the bayespec `config.py` idiom: importing it applies the
`REPRO_*` environment knobs exactly once (idempotence guard), BEFORE
jax initializes, and everything else imports from here instead of
touching `os.environ` itself. The repo-wide invariant, enforced by
tests/test_platform.py and the grep gate in CI review:

    no jax-affecting `os.environ[...]` mutation outside this file.

Environment knobs consumed by `apply()`:

    REPRO_TEST_DEVICES=N   force N host devices (merged into XLA_FLAGS;
                           an operator-set count in XLA_FLAGS wins)
    REPRO_XLA_FLAGS=...    extra XLA flags appended (existing flags of
                           the same name win -- append never clobbers)
    REPRO_X64=1|0          jax x64 mode (via JAX_ENABLE_X64, setdefault)
    REPRO_PLATFORM=cpu|... pin the jax platform (via JAX_PLATFORMS,
                           setdefault)
    REPRO_SEED=N           deterministic seed for benches/harnesses
                           (`default_seed()`)
    REPRO_AUTOTUNE_CACHE   autotune disk-cache path ("" disables;
                           resolved by `autotune_cache_path()`)

JAX's persistent compile cache: where `JAX_COMPILATION_CACHE_DIR` is
set, jax reads it itself and no directory is set in code; otherwise
apply() points the cache at the fixed `<checkout>/.jax_cache`
(`compile_cache_dir()`), so every process of one checkout, and a later
run of it, finds what an earlier one compiled.

`describe()` snapshots the resolved environment (backend, device count,
x64, flags, seed, what apply() changed) for BENCH json rows, serve
stats, and metrics streams -- so every recorded number carries the
environment it was measured under. `is_main()` is the HomebrewNLP-style
rank-0 guard (`jax.process_index() == 0`) that the metrics emitter and
the future multi-host path share.

jax is imported only after the env knobs are applied (the compile-cache
setting, describe, is_main) and no backend is created at import:
importing this module must stay legal BEFORE jax init, which is the
whole point.
"""
from __future__ import annotations

import os
import pathlib
import sys
import warnings
from typing import MutableMapping, Optional

_FORCE_FLAG = "xla_force_host_platform_device_count"

#: the checkout this package runs from (src/repro/platform.py -> root)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]

#: what apply() changed, keyed by knob -- doubles as the idempotence
#: guard (a non-None value means apply() already ran for this process)
_APPLIED: Optional[dict] = None


# ----------------------------------------------------------- flag merge

def _get_flags(env: MutableMapping) -> str:
    return env.get("XLA_FLAGS", "")


def _flag_value(flags: str, name: str) -> Optional[str]:
    """Value of `--name=value` in an XLA_FLAGS string, or None."""
    for tok in flags.split():
        if tok.startswith(f"--{name}="):
            return tok.split("=", 1)[1]
        if tok == f"--{name}":
            return ""
    return None


def _merge_xla_flag(name: str, value, env: MutableMapping) -> str:
    """Append `--name=value` to XLA_FLAGS unless the flag is already
    present -- an operator-set flag ALWAYS wins (append/merge, never
    clobber). Returns the effective value (existing or appended)."""
    flags = _get_flags(env)
    existing = _flag_value(flags, name)
    if existing is not None:
        return existing
    env["XLA_FLAGS"] = (flags + " " if flags else "") + f"--{name}={value}"
    return str(value)


def _jax_initialized() -> bool:
    """Best-effort: has a jax backend already been created (at which
    point XLA_FLAGS mutations are ignored)? Version-tolerant."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:                      # pragma: no cover - jax drift
        return False


def force_host_devices(n: int, env: Optional[MutableMapping] = None) -> int:
    """Merge `--xla_force_host_platform_device_count=n` into XLA_FLAGS.

    Must run before jax first initializes (the same contract the old
    per-entry-point blocks had); warns when it cannot take effect. An
    operator-set count in XLA_FLAGS wins over `n` -- callers get the
    EFFECTIVE count back so they can assert on it. This is the one
    implementation behind conftest's REPRO_TEST_DEVICES.
    """
    env = os.environ if env is None else env
    if env is os.environ and _jax_initialized() \
            and _flag_value(_get_flags(env), _FORCE_FLAG) != str(n):
        warnings.warn(
            f"force_host_devices({n}) after jax initialized its "
            f"backend: XLA_FLAGS changes are ignored now; set "
            f"REPRO_TEST_DEVICES or import repro.platform earlier",
            RuntimeWarning, stacklevel=2)
    return int(_merge_xla_flag(_FORCE_FLAG, int(n), env))


def forced_host_devices(env: Optional[MutableMapping] = None
                        ) -> Optional[int]:
    """The forced host device count currently in XLA_FLAGS, or None."""
    env = os.environ if env is None else env
    v = _flag_value(_get_flags(env), _FORCE_FLAG)
    try:
        return int(v) if v else None
    except ValueError:
        return None


# ---------------------------------------------------------------- apply

def apply(env: Optional[MutableMapping] = None,
          force: bool = False) -> dict:
    """Consume the REPRO_* knobs exactly once per process.

    Importing this module calls apply() -- every entry point that does
    `import repro.platform` (directly or via repro.api / the serve
    engine) gets the same resolved environment. Re-entry is a no-op
    returning the first application's record; `force=True` re-applies
    (used with an explicit `env` by tests -- applying twice is safe
    because every mutation is a merge or a setdefault).
    """
    global _APPLIED
    if _APPLIED is not None and not force and env is None:
        return _APPLIED
    env = os.environ if env is None else env
    applied: dict = {}

    n = env.get("REPRO_TEST_DEVICES")
    if n:
        applied["forced_host_devices"] = force_host_devices(int(n), env)

    extra = env.get("REPRO_XLA_FLAGS")
    if extra:
        merged = []
        for tok in extra.split():
            name = tok.lstrip("-").split("=", 1)[0]
            value = tok.split("=", 1)[1] if "=" in tok else ""
            merged.append(f"--{name}={_merge_xla_flag(name, value, env)}")
        applied["xla_flags_extra"] = " ".join(merged)

    x64 = env.get("REPRO_X64")
    if x64 is not None:
        # setdefault: an explicit JAX_ENABLE_X64 from the operator wins
        env.setdefault("JAX_ENABLE_X64", "1" if x64 == "1" else "0")
        applied["x64"] = env["JAX_ENABLE_X64"] == "1"

    plat = env.get("REPRO_PLATFORM")
    if plat:
        env.setdefault("JAX_PLATFORMS", plat)
        applied["jax_platforms"] = env["JAX_PLATFORMS"]

    cache = compile_cache_dir(env)
    if cache is not None:
        applied["compile_cache_dir"] = cache
        if env is os.environ:
            # last: jax reads JAX_PLATFORMS when it is first imported
            import jax
            jax.config.update("jax_compilation_cache_dir", cache)

    if env is os.environ:
        _APPLIED = applied
    return applied


def compile_cache_dir(env: Optional[MutableMapping] = None
                      ) -> Optional[str]:
    """The compile-cache directory this module sets in code: None when
    $JAX_COMPILATION_CACHE_DIR is set (jax uses it as given), else the
    fixed `<checkout>/.jax_cache`. Never a temporary, per-process or
    per-run name, so that later runs and other processes of the same
    checkout find the entries."""
    env = os.environ if env is None else env
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(CHECKOUT / ".jax_cache")


def hermetic_autotune(env: Optional[MutableMapping] = None) -> None:
    """Disable the autotune DISK cache unless the operator pointed
    REPRO_AUTOTUNE_CACHE somewhere explicitly (setdefault to ""):
    tests and benches must probe live, not inherit a stale ~/.cache
    decision from a previous run."""
    (os.environ if env is None else env).setdefault(
        "REPRO_AUTOTUNE_CACHE", "")


def autotune_cache_path(env: Optional[MutableMapping] = None
                        ) -> Optional[str]:
    """Resolved autotune disk-cache path: $REPRO_AUTOTUNE_CACHE if set
    ("" disables -> None), else ~/.cache/repro/autotune.json. The one
    resolution core/autotune_cache.py consumes."""
    env = os.environ if env is None else env
    p = env.get("REPRO_AUTOTUNE_CACHE")
    if p is not None:
        return os.path.expanduser(p) if p else None
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def default_seed(env: Optional[MutableMapping] = None) -> int:
    """Deterministic-seed plumbing: $REPRO_SEED, default 0. Benches and
    harnesses derive their numpy/jax streams from this so a CI lane can
    replay a run exactly by exporting one variable."""
    env = os.environ if env is None else env
    try:
        return int(env.get("REPRO_SEED", "0"))
    except ValueError:
        return 0


# ------------------------------------------------------------- snapshot

def is_main() -> bool:
    """Rank-0 guard (`jax.process_index() == 0`): only the main process
    of a multi-host mesh logs, checkpoints, and emits metrics. True on
    single-process deployments and when jax is unavailable."""
    try:
        import jax
        return jax.process_index() == 0
    except Exception:
        return True


def describe() -> dict:
    """Snapshot of the resolved platform: what environment did this
    measurement/serve run under? Touches jax device state (initializes
    the backend if nothing else has), so callers on the pre-init path
    must not describe() before their flags are set -- benches call it
    at record time, the serve engine at construction."""
    import platform as host
    import jax
    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "device_kind": str(getattr(dev, "device_kind", "?")),
        "process_index": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "x64": bool(jax.config.jax_enable_x64),
        "jax_version": jax.__version__,
        "machine": host.machine(),
        "python": host.python_version(),
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "forced_host_devices": forced_host_devices(),
        "autotune_cache": autotune_cache_path(),
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "seed": default_seed(),
        "applied": dict(_APPLIED or {}),
    }


def _reset_for_tests() -> None:
    global _APPLIED
    _APPLIED = None


# one application per process, at first import -- the module IS the seam
apply()
