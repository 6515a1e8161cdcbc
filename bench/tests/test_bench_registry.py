"""Cells, configurations, mixes, limits and metric readers are found by
name, and a new file is picked up without editing any existing one."""
import json
import shutil

import pytest

from bench import peaks, registry


def test_every_cell_resolves():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.config["num_classes"] > 0
        assert cell.traffic["trainer"] in ("astraea", "fedavg")
        lim = registry.limits(w["name"])
        assert set(lim) == {"schedule_mismatch", "loss_gap", "update1_gap",
                            "update3_gap"}
        assert {m["name"] for m in cell.end_to_end} >= {"round_s",
                                                        "setup_s"}
        for m in cell.per_layer:
            assert callable(registry.metric_reader(m["name"]))


def test_metrics_of_a_cell_follow_their_workloads_lists(tmp_path):
    names = {m["name"] for m in
             registry.cell("emnist-ltrf.astraea").per_layer}
    assert {"warp_ms", "warp_roofline", "mfu"} <= names
    bench = registry.benchmark()
    for m in bench["per_layer"]:
        if m["name"] == "warp_ms":
            m["workloads"] = ["another.cell"]
    shutil.copytree(registry.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    names = {m["name"] for m in
             registry.cell("emnist-ltrf.astraea", root=tmp_path).per_layer}
    assert "warp_ms" not in names and "mfu" in names


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="unknown workload"):
        registry.cell("no-such.cell")


def test_new_files_are_picked_up_without_edits(tmp_path):
    """A later PR adds a configuration, a mix, a metric reader and limits
    as new files plus entries in BENCHMARK.json: nothing else changes."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = registry.benchmark()
    cfg = registry.config("emnist-ltrf")
    cfg["num_clients"] = 1000
    (root / "bench/configs/emnist-wide.json").write_text(json.dumps(cfg))
    mix = registry.traffic("astraea")
    mix["clients_per_round"] = 100
    (root / "bench/traffic/astraea-c100.json").write_text(json.dumps(mix))
    (root / "bench/limits/emnist-wide.astraea-c100.json").write_text(
        json.dumps({"limits": registry.limits("emnist-ltrf.astraea")}))
    (root / "bench/metrics/rounds_seen.py").write_text(
        "def read(run):\n    return float(len(run.schedules))\n")
    bench["workloads"].append({"name": "emnist-wide.astraea-c100",
                               "config": "emnist-wide",
                               "traffic": "astraea-c100", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine host path",
                               "moves": "round_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = registry.cell("emnist-wide.astraea-c100", root=root)
    assert cell.config["num_clients"] == 1000
    assert cell.traffic["clients_per_round"] == 100
    names = [m["name"] for m in cell.per_layer]
    assert "rounds_seen" in names          # no workloads key: every cell
    reader = registry.metric_reader("rounds_seen", root / "bench")

    class Run:
        schedules = [[[1]], [[2]]]
    assert reader(Run) == 2.0
    assert "rounds_seen" in [m["name"] for m in
                             registry.cell("emnist-ltrf.astraea",
                                           root=root).per_layer]


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")
