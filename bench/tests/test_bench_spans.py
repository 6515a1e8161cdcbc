"""The program's spans on the device clock: idle by intersection with
nested spans, reads apart from the host's own work, chips averaged, and
the annotations of a real CPU profile with telemetry off."""
import pytest

from bench import spans, xplane


def _reduce():
    # chip 0 idles [0,10] [20,50] [60,100]; chip 1 idles [0,10] [40,100]
    device = {"/device:TPU:0": [("a", 10, 20), ("b", 50, 60)],
              "/device:TPU:1": [("a", 10, 40)]}
    host = [("bench.run_round", 0, 100),
            ("astraea.round", 5, 95),
            ("astraea.reschedule", 20, 70),
            ("astraea.host_read.alg3_picks", 30, 55),
            ("np.asarray(jax.Array)", 31, 54),
            ("astraea.schedule_stats", 56, 70),
            ("astraea.host_read.kld", 60, 63),
            ("astraea.host_read.kld", 63, 66),
            ("astraea.round_program", 70, 90)]
    return spans.reduce_spans(device, host)


def test_idle_is_intersected_with_every_span_and_averaged_over_chips():
    r = _reduce()
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_s"] == pytest.approx((80 + 70) / 2 * 1e-9)
    t = r["spans"]
    # [30,55]: chip 0 idles 20 of it (to 50), chip 1 idles 15 (from 40)
    assert t["host_read.alg3_picks"] == pytest.approx(
        {"count": 1, "seconds": 25e-9, "idle_s": 17.5e-9})
    # the parent holds its children's idle and its own: [20,70]
    assert t["reschedule"]["idle_s"] == pytest.approx((40 + 30) / 2 * 1e-9)
    assert t["host_read.kld"] == pytest.approx(
        {"count": 2, "seconds": 6e-9, "idle_s": 6e-9})
    assert t["round"]["idle_s"] == pytest.approx((70 + 60) / 2 * 1e-9)
    # a host event of the runtime is no span of the program
    assert "np.asarray(jax.Array)" not in t


def test_reads_and_the_hosts_own_work_split_the_round_idle():
    r = _reduce()
    assert r["read_idle_s"] == pytest.approx(23.5e-9)
    assert r["bubble_idle_s"] == pytest.approx(65e-9 - 23.5e-9)
    assert r["read_idle_s"] + r["bubble_idle_s"] == pytest.approx(
        r["round_idle_s"])
    (kids,) = r["rounds"]
    assert kids["round"] == pytest.approx(90e-6)
    assert kids["host_read.kld"] == pytest.approx(6e-6)
    got = spans.per_round(r)
    assert got["host_reads"] == 3.0
    assert got["alg3_wait_ms"] == pytest.approx(25e-6)
    assert got["read_idle_ms"] == pytest.approx(23.5e-6)
    assert got["host_bubble_ms"] == pytest.approx(41.5e-6)


def test_without_device_plane_or_round_spans():
    host = [("bench.x", 0, 10), ("astraea.round", 2, 8),
            ("astraea.host_read.kld", 3, 4)]
    r = spans.reduce_spans({}, host)
    assert r["round_idle_s"] == pytest.approx(6e-9)     # nothing ran
    assert spans.per_round(r)["alg3_wait_ms"] is None
    assert spans.per_round(spans.reduce_spans({}, host[:1])) is None
    assert spans.reduce_spans({}, [])["window_s"] == 0.0


@pytest.mark.parametrize("traffic", ["astraea", "fedavg"])
def test_round_spans_reach_the_profiler_with_telemetry_off(
        traffic, cpu_device, tmp_path):
    """Two profiled rounds of a tiny trainer with telemetry off: the
    annotations are in the ``.xplane.pb``, one Alg. 3 read and one KLD
    read per mediator a round for Astraea, none for FedAvg."""
    import jax
    from bench import federation, program, reference
    from bench.tests.conftest import tiny_cell
    from repro.obs import NULL_TELEMETRY
    cell = tiny_cell("emnist-ltrf.astraea", traffic)
    cfg, mix = cell.config, cell.traffic
    fed = federation.generate(cfg, 5)
    tr = program.build_trainer(
        cfg, mix, fed, 5, reference.make_init(cfg)(jax.random.PRNGKey(5)),
        devices=cpu_device)
    tr.run_round()                       # compile outside the window
    assert tr.engine.telemetry is NULL_TELEMETRY
    spans.profiled_rounds(tr, 2, str(tmp_path))
    (path,) = tmp_path.rglob("*.xplane.pb")
    device, host, _ = xplane.read_xplane(str(path))
    names = [n for n, _, _ in host if n.startswith("astraea.")]
    reads = [n for n in names if n.startswith("astraea.host_read.")]
    m = len(tr.engine.last_groups)
    assert names.count("astraea.round") == 2
    assert names.count("astraea.round_program") == 2
    if traffic == "astraea":
        assert m == 2
        assert names.count("astraea.host_read.alg3_picks") == 2
        assert len(reads) == 2 * (1 + m)
    else:
        assert reads == []
    got = spans.per_round(spans.reduce_spans(device, host))
    assert got["host_reads"] == len(reads) / 2
