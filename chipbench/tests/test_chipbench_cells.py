"""Everything a cell is made of is found by name, and the benchmark
refuses any platform but a TPU it has peaks for."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import cells  # noqa: E402

BENCH = cells.benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    cell = cells.cell(name, BENCH)
    entry = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["kind"] in ("open", "closed")
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]).read)
        assert m["moves"] in [e["name"] for e in cell.end_to_end]


def test_every_config_file_is_named_by_benchmark_json():
    for c in BENCH["configs"]:
        cfg = cells.config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert (ROOT / cfg["svm"]).is_file()


def test_costs_and_peaks_are_found_by_name():
    for c in BENCH["configs"]:
        kernels = cells.config(c["name"])["costs"]
        assert set(cells.costs(kernels)) == set(kernels)
    with pytest.raises(KeyError, match="no cost file"):
        cells.costs(["no_such_kernel"])
    assert cells.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        cells.peaks("TPU v9 imaginary")


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        cells.cell("no_such.cell", BENCH)
    with pytest.raises(KeyError):
        cells.metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        cells.metric_reader("no_such_metric.hd")


def test_a_suffixed_metric_is_read_by_its_kind():
    assert cells.metric_reader("idle_share.any_new_cell").read is \
        not None
    assert cells.metric_reader("hog_roofline.hd").__file__.endswith(
        "metrics/hog_roofline.py")
    with pytest.raises(KeyError):
        cells.config("no_such_config")


def _run(code: str, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


def test_a_cpu_platform_is_refused_without_a_result():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_an_unknown_device_kind_is_refused_without_a_result():
    code = (
        "import sys, jax\n"
        "sys.argv = ['run.py', '--workload', %r, '--seed', '1',"
        " '--seconds', '1']\n"
        "class Dev:\n"
        "    platform = 'tpu'\n"
        "    device_kind = 'TPU v9 imaginary'\n"
        "jax.devices = lambda *a: [Dev()]\n"
        "sys.path.insert(0, 'chipbench')\n"
        "import run\n"
        "sys.exit(run.main())\n") % BENCH["workloads"][0]["name"]
    p = _run(code)
    assert p.returncode == 2, p.stderr
    assert "not in chipbench/peaks.json" in p.stderr
    assert p.stdout.strip() == ""


def test_importing_the_benchmark_starts_no_backend():
    code = (
        "import importlib, pathlib, sys\n"
        "sys.path[:0] = ['src', '.', 'chipbench']\n"
        "for p in sorted(pathlib.Path('chipbench').glob('*.py')):\n"
        "    importlib.import_module('chipbench.' + p.stem)\n"
        "from chipbench import cells\n"
        "for m in cells.benchmark()['per_layer']:\n"
        "    cells.metric_reader(m['name'])\n"
        "for c in cells.benchmark()['configs']:\n"
        "    cells.costs(cells.config(c['name'])['costs'])\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))\n")
    p = _run(code)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "0"


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(BENCH)) < 64 * 1024
