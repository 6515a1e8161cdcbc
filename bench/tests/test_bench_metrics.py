"""The per-layer readers on a hand-made run record."""
from types import SimpleNamespace

import pytest

from bench import counting, registry


def _run(**kw):
    cfg = registry.config("emnist-ltrf")
    base = dict(cfg=cfg, traffic=registry.traffic("astraea"),
                counting=counting,
                peaks={"flops": 197e12, "hbm_bytes_per_s": 819e9},
                rounds=2,
                trace={"window_s": 2.0, "busy_s": 1.5,
                       "op_seconds": {"fusion.1": 0.5, "custom-call.7": 0.2},
                       "op_stats": {"custom-call.7":
                                    "custom_call_target=tpu_custom_call"}},
                spans=[{"kind": "span", "name": "reschedule", "dur_us": 3e3},
                       {"kind": "span", "name": "reschedule", "dur_us": 5e3},
                       {"kind": "span", "name": "pack", "dur_us": 1e3},
                       {"kind": "instant", "name": "pack", "dur_us": 0.0}],
                slots=[(5000.0, 135_000)] * 2,
                profile_slots=[(5000.0, 135_000)] * 2,
                profile_wall_s=2.0)
    base.update(kw)
    return SimpleNamespace(**base)


def read(name, run):
    return registry.metric_reader(name)(run)


def test_host_spans():
    assert read("reschedule_ms", _run()) == pytest.approx(4.0)
    assert read("pack_ms", _run()) == pytest.approx(1.0)
    assert read("reschedule_ms", _run(spans=[])) is None


def test_useful_samples_over_padded_slots():
    # 50 clients of 100 samples in 10 rows x 5 slots x 2,700
    assert read("useful_sample_pct", _run()) == pytest.approx(
        100 * 5000 / 135_000)
    assert read("useful_sample_pct", _run(slots=None)) is None


def test_idle_share():
    assert read("device_idle_pct", _run()) == pytest.approx(25.0)


def test_warp_time_and_roofline():
    assert read("warp_ms", _run()) == pytest.approx(100.0)
    images = 5000                               # the real slots alone
    least = images * 12_544 / 819e9             # bytes bound the warp
    assert read("warp_roofline", _run()) == pytest.approx(
        100 * least / 0.1)
    no_kernel = _run(trace={"window_s": 1.0, "busy_s": 0.5,
                            "op_seconds": {"fusion.1": 0.5}, "op_stats": {}})
    assert read("warp_ms", no_kernel) is None
    assert read("warp_roofline", no_kernel) is None
    assert read("warp_roofline", _run(profile_slots=None)) is None


def test_mfu_counts_needed_training_flops_only():
    need = 3 * 368_196 * 5000                   # per round
    assert read("mfu", _run()) == pytest.approx(100 * need / 1.0 / 197e12)
    assert read("mfu", _run(profile_slots=None)) is None


@pytest.mark.parametrize("traffic", ["astraea", "fedavg"])
def test_slot_counts_read_from_the_program(traffic, cpu_device):
    """The real samples and padded slots of a round come from the operands
    the engine fed the round program, and agree with the generated client
    sizes, the round's schedule and the host pack reckoning."""
    import jax
    from bench import federation, program, reference
    from bench import run as bench_run
    from bench.tests.conftest import tiny_cell
    cell = tiny_cell("emnist-ltrf.astraea", traffic)
    cfg, mix = cell.config, cell.traffic
    fed = federation.generate(cfg, 3)
    tr = program.build_trainer(
        cfg, mix, fed, 3, reference.make_init(cfg)(jax.random.PRNGKey(3)),
        devices=cpu_device)
    ops = []
    for _ in range(2):
        tr.run_round()
        ops.append(bench_run.round_operands(tr))
        sizes = fed.counts.sum(axis=1)
        want_real = sum(int(sizes[c]) for g in tr.engine.last_groups
                        for c in g)
        rows = -(-mix["clients_per_round"] // mix["gamma"])
        pad = federation.pack_reckoning(cfg, mix["batch_size"],
                                        fed.counts)["pad"]
        assert bench_run.slot_counts(ops)[-1] == (
            want_real, rows * mix["gamma"] * pad)


def test_traced_run_reports_the_per_layer_metrics(cpu_device, monkeypatch):
    """The ``--trace 1`` path end to end at test size. The CPU has no
    device plane, so only host spans and counters read: the idle share is
    the whole window and the warp reads nothing."""
    import time
    from bench import peaks
    from bench import run as bench_run
    from bench.tests.conftest import tiny_cell
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell("emnist-ltrf.astraea")
    res = bench_run.run_cell(cell, 7, 0.05, True, cpu_device,
                             t0=time.perf_counter(),
                             limits=registry.limits(cell.name))
    assert res["correct"] is True
    got = res["metrics"]
    assert {"reschedule_ms", "pack_ms", "useful_sample_pct", "mfu",
            "device_idle_pct"} <= set(got)
    assert "warp_ms" not in got and "warp_roofline" not in got
    assert got["device_idle_pct"]["value"] == pytest.approx(100.0)
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"
