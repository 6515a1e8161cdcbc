"""The trace reduction: busy/idle union, per-operation time and the
attribution of idle gaps to what the host was doing."""
from pathlib import Path

import pytest

from bench import xplane

FIXTURE = Path(__file__).with_name("fixtures") / "tiny.xplane.pb"


def test_union_counts_overlap_once_and_gaps_go_to_the_innermost_span():
    device = {"/device:TPU:0": [("conv", 10, 20), ("warp", 15, 30),
                                ("conv", 50, 60)]}
    host = [("bench.run_round", 0, 100), ("reschedule", 25, 80),
            ("np.asarray", 35, 45), ("pack", 61, 70)]
    r = xplane.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)          # [10,30] + [50,60]
    assert r["op_seconds"] == pytest.approx({"conv": 20e-9, "warp": 15e-9})
    assert list(r["op_seconds"]) == ["conv", "warp"]
    # [0,10]: only the benchmark's own annotation -> idle;
    # [30,50]: mid 40 inside np.asarray; [60,100]: mid 80 inside reschedule
    assert dict(r["gaps"]) == pytest.approx(
        {"reschedule": 40e-9, "np.asarray": 20e-9, "idle": 10e-9})


def test_busy_is_averaged_over_chips():
    device = {"/device:TPU:0": [("a", 0, 10)],
              "/device:TPU:1": [("a", 0, 30)]}
    r = xplane.reduce_events(device, [("bench.x", 0, 40)])
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["op_seconds"]["a"] == pytest.approx(20e-9)


def test_no_events_reads_an_empty_window():
    r = xplane.reduce_events({}, [])
    assert r["window_s"] == 0.0 and r["busy_s"] == 0.0


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: three steps of the warp kernel
    (64 images) and a 512x512 matmul, each in a ``bench.step`` span."""
    r = xplane.reduce_file(str(FIXTURE))
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(r["op_seconds"].values()) >= r["busy_s"] * 0.999
    assert any("tpu_custom_call" in r["op_stats"].get(n, "") or
               "tpu_custom_call" in n for n in r["op_seconds"])
    assert sum(s for _, s in r["gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
