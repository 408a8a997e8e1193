"""The detection path does not load the LM scaffold.

Each entry module is imported in a fresh interpreter, and the modules
it leaves in `sys.modules` must hold no LM model, trainer, sharding
rule, architecture registry, attention kernel or token pipeline.
`repro.kernels` is imported at first use by the kernel backends.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

LM_PREFIXES = ("repro.models", "repro.train", "repro.sharding",
               "repro.configs.registry",
               "repro.kernels.flash_attention", "repro.data.lm_data")

_PROBE = """
import importlib, json, sys
importlib.import_module(sys.argv[1])
print(json.dumps(sorted(sys.modules)))
"""


@pytest.mark.parametrize("module", ["repro.api", "repro.serve.engine",
                                    "repro.launch.serve", "repro.kernels"])
def test_detection_module_loads_no_lm_code(module):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (SRC, os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run([sys.executable, "-c", _PROBE, module],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True).stdout
    loaded = json.loads(out.splitlines()[-1])
    lm = [m for m in loaded
          if any(m == p or m.startswith(p + ".") for p in LM_PREFIXES)]
    assert not lm, f"{module} loads {lm}"
