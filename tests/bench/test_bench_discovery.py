"""The benchmark finds each cell's files by name, and refuses to run
without a chip."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_its_files_by_name(workload):
    cell = harness.resolve(workload)
    assert os.path.isfile(harness.system_path(cell.config))
    assert cell.traffic["loop"] in ("open", "closed")
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        reader = harness.load_module(harness.metric_path(m["name"]))
        assert callable(reader.read)
    for m in cell.per_layer:
        assert m["moves"] in names


def test_spec_keeps_to_its_own_rules():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert layers == {"kernels", "device", "entry"}
    assert all(c["chips"] == 1 for c in SPEC["workloads"])
    used = {c["config"] for c in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert {m["moves"] for m in SPEC["per_layer"]} <= e2e
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_new_cell_is_entries_only(tmp_path):
    # a cell made of files that are already there needs no code changed
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(spec["workloads"][0],
                                  name="serve_again"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "serve_overload" in m.get("workloads", []):
            m["workloads"].append("serve_again")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" /
                    "configs")
    cell = harness.resolve("serve_again", root=str(tmp_path))
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                     "fits_per_s"}
    with pytest.raises(KeyError):
        harness.resolve("no_such_cell", root=str(tmp_path))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve_overload",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_off_the_chip_it_fails_and_prints_no_result():
    got = _run(ROOT)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "no TPU" in got.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", "out"))
    env = {"PYTHONPATH": ""}
    got = _run(tmp_path, env)
    assert got.returncode != 0
    assert got.stdout.strip() == ""


def test_a_split_metric_is_read_by_its_quantity():
    assert harness.metric_path("device_idle.fit").endswith(
        os.path.join("metrics", "device_idle.py"))
    assert harness.metric_path("fit_ms").endswith(
        os.path.join("metrics", "fit_ms.py"))
