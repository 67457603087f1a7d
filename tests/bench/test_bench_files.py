"""The benchmark's files agree with each other and with BENCHMARK.json, the
harness takes a cell of a new traffic kind as new files only, and it
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.run import load_module  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
# files of a toy cell of a kind of its own, laid out as in the benchmark tree
TOY = Path(__file__).with_name("toy_kind")


def _json(path):
    return json.loads(Path(path).read_text())


def check_cell_files(root, bench, cell):
    """The cell's files exist, agree with its entry in ``bench``, and give a
    limit to each check its traffic kind declares in ``CHECKS``."""
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    wl = _json(root / "bench" / "workloads" / f"{cell}.json")
    for key in ("config", "traffic", "chips", "why"):
        assert wl[key] == entry[key], key
    cfg = _json(root / "bench" / "configs" / f"{wl['config']}.json")
    assert (root / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
    traffic = _json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    kind = root / "bench" / "traffic" / f"{traffic['kind']}.py"
    assert kind.is_file()
    assert set(wl["limits"]) == set(load_module(kind).CHECKS)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    assert conf["file"] == f"bench/configs/{wl['config']}.json"
    assert conf["source"] == cfg["source"] and conf["reduced"] == cfg["reduced"]


def check_tree(root, bench):
    """Every configuration is used, every workload file is a cell, and at
    most half the cells (or one) take four chips."""
    cells = [w["name"] for w in bench["workloads"]]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    on_disk = {p.stem for p in (root / "bench" / "workloads").glob("*.json")}
    assert on_disk == set(cells)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)


def check_reports(end_to_end, per_layer):
    assert "setup_s" in end_to_end and len(set(end_to_end)) >= 2
    assert per_layer


def _reports(cell):
    """End-to-end metric names a cell reports."""
    return {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_workload_names_files_that_exist(cell):
    check_cell_files(ROOT, BENCH, cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_agrees_with_its_cells(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert (ROOT / "bench" / "metrics" / f"{metric}.py").is_file()
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert m["workloads"], "a per-layer metric lists the cells it is read in"
    for cell in m["workloads"]:
        assert cell in CELLS
        assert m["moves"] in _reports(cell), (cell, m["moves"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(cell):
    from bench.run import load_cell

    c = load_cell(cell)
    check_reports([m["name"] for m in c.end_to_end], [m["name"] for m in c.per_layer])
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x for x in layers)


def test_every_config_is_used_and_every_file_is_named():
    check_tree(ROOT, BENCH)


# -- a cell of a new traffic kind, added as new files only ---------------------

@pytest.fixture(scope="module")
def toy_tree(tmp_path_factory):
    """A copy of the benchmark tree with the toy kind's files planted and its
    entries added to BENCHMARK.json; no file the copy had is changed."""
    tree = tmp_path_factory.mktemp("tree")
    shutil.copytree(ROOT / "bench", tree / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tree / "src").symlink_to(ROOT / "src")
    for path in sorted((TOY / "bench").rglob("*")):
        if path.is_file():
            dest = tree / path.relative_to(TOY)
            assert not dest.exists(), f"{dest} would edit a file of the tree"
            shutil.copyfile(path, dest)
    bench = json.loads(json.dumps(BENCH))
    add = _json(TOY / "benchmark_additions.json")
    bench["configs"] += add["configs"]
    bench["workloads"] += add["workloads"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.get("workloads", []).extend(add["cells_of_metric"].get(metric["name"], []))
    (tree / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return tree, bench


@pytest.fixture(scope="module")
def toy_run(toy_tree):
    """The toy cell loaded and run by the copy's own harness, and run again
    under a kind that reports an undeclared check, and one that drops its
    declared check."""
    tree, _ = toy_tree
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).with_name("_toy_child.py")), str(tree),
         "toy_off,extra_off", ""],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_new_kind_cell_passes_the_file_checks(toy_tree):
    tree, bench = toy_tree
    check_cell_files(tree, bench, "toy.glass")
    check_tree(tree, bench)


def test_new_kind_cell_loads_and_runs_correct(toy_run):
    check_reports(toy_run["end_to_end"], toy_run["per_layer"])
    out = toy_run["run"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0, out
    assert out["checks"] == {"toy_off": {"value": 0.0, "limit": 0}}
    assert set(out["metrics"]) == {"flips_per_s", "setup_s"}


@pytest.mark.parametrize("report", ["toy_off,extra_off", ""])
def test_record_whose_checks_differ_from_its_kinds_is_refused(toy_run, report):
    err = toy_run["forged"][report]
    assert err is not None and "declares ['toy_off']" in err, err


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0], "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
