"""Every cell of BENCHMARK.json resolves to files of its own by name, the
file keeps to the benchmark's contract, and the CLI refuses to run
without a TPU."""
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
sys.path.insert(0, str(HERE))

import spec  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(len(w["why"]) <= 200 for w in BENCH["workloads"])


def test_every_config_is_used_and_has_its_own_file():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = spec.config(c["name"])
        assert cfg["source"] == c["source"]
        assert c["reduced"] == sorted(cfg["reduced"])
        (REPO / c["file"]).resolve().relative_to(HERE)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.cell(name)
    wl = cell["workload"]
    assert wl["why"] == [w for w in BENCH["workloads"]
                         if w["name"] == name][0]["why"]
    assert cell["traffic"]["sessions"] >= wl["engine"]["max_batch"]
    assert wl["limits"]["max_logit_gap"] > 0
    spec.reference(cell["config"]["family"])
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert m["moves"] in e2e
        assert callable(spec.metric(m["name"]).read)
    for kernel in ("paged_attention", "codec"):
        cost = spec.cost(kernel)
        assert cost.NAMES


def test_peaks_hold_the_recorded_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    out = _cli(REPO)
    assert out.returncode != 0
    assert "no CPU fallback" in out.stderr
    assert '"correct"' not in out.stdout


def test_cli_alone_fails(tmp_path):
    """A directory with BENCHMARK.json and chipbench/ only has no program
    to run: the command exits nonzero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
