"""The benchmark's files agree with each other and with BENCHMARK.json, and
the harness refuses to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
# what each traffic kind's check reports; a cell gives each a limit
KIND_CHECKS = {
    "session_window": {"replicas_off", "rungs_off", "energy_off"},
}


def _json(path):
    return json.loads(Path(path).read_text())


def _reports(cell):
    """End-to-end metric names a cell reports."""
    return {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_workload_names_files_that_exist(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    wl = _json(ROOT / "bench" / "workloads" / f"{cell}.json")
    for key in ("config", "traffic", "chips", "why"):
        assert wl[key] == entry[key], key
    cfg = _json(ROOT / "bench" / "configs" / f"{wl['config']}.json")
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
    traffic = _json(ROOT / "bench" / "traffic" / f"{wl['traffic']}.json")
    assert (ROOT / "bench" / "traffic" / f"{traffic['kind']}.py").is_file()
    assert set(wl["limits"]) == KIND_CHECKS[traffic["kind"]]
    conf = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert conf["file"] == f"bench/configs/{wl['config']}.json"
    assert conf["source"] == cfg["source"] and conf["reduced"] == cfg["reduced"]


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
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x for x in layers)


def test_every_config_is_used_and_every_file_is_named():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    on_disk = {p.stem for p in (ROOT / "bench" / "workloads").glob("*.json")}
    assert on_disk == set(CELLS)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0], "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
