"""Every cell of BENCHMARK.json resolves by name to its files, and a cell
added as files and entries needs no edit to the harness."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, check, counts, harness, traffic_gen  # noqa: E402

BENCH = cells.benchmark(ROOT)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_by_name(name):
    cell = cells.load(name, ROOT)
    cfg = cell.config
    assert cfg["name"] == name.split(".")[0]
    assert counts.n_params(cfg["model"]) == cfg["model"]["params_per_client"]
    assert set(check.NUMBERS) <= set(cell.limits)
    assert {m["name"] for m in cell.end_to_end} >= {
        "setup_s", "client_updates_per_s"}
    for metric, read in cell.readers().items():
        assert read({"trace": None}) is None, metric
    # the configuration is one FedConfig, and the traffic one schedule
    fed = harness.fed_config(cfg)
    assert fed.n_clients == cfg["fleet"]["n_clients"]
    sched = traffic_gen.schedule(cell.traffic, 50, 123, 4)
    assert sched.n_rounds == 4 and sched.n_clients == 50


def test_config_files_are_distinct_and_unreduced():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_a_cell_added_as_files_needs_no_harness_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bench = json.loads(json.dumps(BENCH))
    # a new traffic mix: a data file only
    with open(root / "bench" / "traffic" / "quorum-0.6.json") as f:
        traffic = json.load(f)
    traffic.update(name="quorum-0.1",
                   trigger={"kind": "quorum", "active_frac": 0.1})
    with open(root / "bench" / "traffic" / "quorum-0.1.json", "w") as f:
        json.dump(traffic, f)
    # a new per-layer metric: a reader of its own
    (root / "bench" / "metrics" / "updates_per_round.py").write_text(
        "def read(record):\n"
        "    return record['updates'] / max(record['rounds'], 1)\n")
    bench["workloads"].append({
        "name": "trento-h24.quorum-0.1", "config": "trento-h24",
        "traffic": "quorum-0.1", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "updates_per_round", "unit": "updates", "better": "higher",
        "source": "program_counter", "layer": "round",
        "moves": "client_updates_per_s"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    cell = cells.load("trento-h24.quorum-0.1", str(root))
    assert cell.traffic["trigger"]["active_frac"] == 0.1
    sched = traffic_gen.schedule(cell.traffic, 40, 5, 3)
    assert list(sched.arrivals) == [4, 4, 4]
    names = [m["name"] for m in cell.per_layer]
    assert "updates_per_round" in names
    read = cell.readers()["updates_per_round"]
    assert read({"updates": 60, "rounds": 6}) == 10
