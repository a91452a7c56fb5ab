"""The benchmark finds each cell's files by name, keeps to its rules on
chips and layers, and refuses to run without a chip."""
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


# the layers of PERF.md's section 3, from the entry point to the chip
LAYERS = {"entry", "plan", "kernels", "collective", "device"}


def keep_to_the_rules(spec: dict, root: pathlib.Path) -> None:
    """Raise AssertionError where ``spec`` breaks one of the rules its
    cells and metrics keep to."""
    cells = [c["name"] for c in spec["workloads"]]
    layers = {m["layer"] for m in spec["per_layer"]}
    assert layers <= LAYERS, layers - LAYERS
    assert all(c["chips"] in (1, 4) for c in spec["workloads"])
    four = sum(c["chips"] == 4 for c in spec["workloads"])
    assert four <= max(1, len(cells) // 2), f"{four} cells of four chips"
    used = {c["config"] for c in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert {m["moves"] for m in spec["per_layer"]} <= e2e
    for c in spec["configs"]:
        assert (root / c["file"]).is_file()


def test_spec_keeps_to_its_own_rules():
    keep_to_the_rules(SPEC, ROOT)


def _with_a_sharded_cell(tmp_path) -> dict:
    """The spec with a third cell, of four chips, and a metric of the
    ``collective`` layer, its configuration's file under ``tmp_path``."""
    spec = json.loads(json.dumps(SPEC))
    file = "bench/configs/sharded-series.json"
    (tmp_path / file).parent.mkdir(parents=True)
    (tmp_path / file).write_text("{}")
    for c in spec["configs"]:
        (tmp_path / c["file"]).write_text("{}")
    spec["configs"].append({"name": "sharded-series", "file": file})
    spec["workloads"].append({"name": "sharded_fit",
                              "config": "sharded-series",
                              "traffic": "closed_loop", "chips": 4})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("sharded_fit")
    spec["per_layer"].append({
        "name": "collective_share.sharded_fit", "unit": "%",
        "better": "lower", "source": "device_trace", "layer": "collective",
        "moves": "fits_per_s", "workloads": ["sharded_fit"]})
    return spec


def test_the_rules_take_a_four_chip_cell_and_a_collective_metric(tmp_path):
    keep_to_the_rules(_with_a_sharded_cell(tmp_path), tmp_path)


@pytest.mark.parametrize("breach", ["two_cells_of_four_chips",
                                    "three_chips", "a_layer_not_listed"])
def test_the_rules_refuse_a_spec_that_breaks_them(tmp_path, breach):
    spec = _with_a_sharded_cell(tmp_path)
    if breach == "two_cells_of_four_chips":
        spec["workloads"][0]["chips"] = 4
    elif breach == "three_chips":
        spec["workloads"][-1]["chips"] = 3
    else:
        spec["per_layer"][-1]["layer"] = "network"
    with pytest.raises(AssertionError):
        keep_to_the_rules(spec, tmp_path)


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
    assert harness.metric_path("fit_roofline.large_fit").endswith(
        os.path.join("metrics", "fit_roofline.py"))
    assert harness.metric_path("device_idle.serve_overload").endswith(
        os.path.join("metrics", "device_idle.py"))
    assert harness.metric_path("setup_s").endswith(
        os.path.join("metrics", "setup_s.py"))
