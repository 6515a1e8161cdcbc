"""The obs subsystem's contract (src/repro/obs + launch/metrics_endpoint).

The non-negotiable invariant: telemetry is measurement, never treatment.
Turning it on must leave trajectories bitwise identical, add zero round
traces, and keep every ledger equality exact -- the Prometheus WAN sample
IS ``CommMeter.total_bytes``, the wave span charges sum to the paper's
per-round formulas. These tests pin that contract for every client-store
placement policy, sync and async.
"""
import json
import math
import urllib.request

import jax
import numpy as np
import pytest

from repro.core import LocalSpec
from repro.core.astraea import AstraeaTrainer
from repro.launch.mesh import make_mediator_mesh
from repro.launch.metrics_endpoint import CONTENT_TYPE, MetricsServer
from repro.models.cnn import count_params, emnist_cnn
from repro.core import scheduling
from repro.obs import (NULL_TELEMETRY, SCHEMA_VERSION, Telemetry, Tracer,
                       host_read, load_jsonl, validate_events)
from repro.obs.metrics import Counter, Histogram, MetricsRegistry
from repro.optim import adam

C, GAMMA, EM = 6, 3, 1


@pytest.fixture(scope="module")
def model(tiny_federation):
    return emnist_cnn(tiny_federation.num_classes, image_size=16)


def _trainer(model, fed, store, s_bound, telemetry):
    kw = {}
    if s_bound is not None:
        from repro.core.async_engine import AsyncSpec
        from repro.core.staleness import StragglerSpec
        kw["async_spec"] = AsyncSpec(
            staleness_bound=s_bound, wave_size=1,
            straggler=StragglerSpec(model="fixed", straggler_frac=0.25,
                                    slowdown=4.0, seed=0))
    return AstraeaTrainer(model, adam(1e-3), fed, clients_per_round=C,
                          gamma=GAMMA, local=LocalSpec(10, EM), alpha=None,
                          seed=0, store=store, mesh=make_mediator_mesh(1),
                          telemetry=telemetry, **kw)


def _run(model, fed, store, s_bound, telemetry, rounds=2):
    tr = _trainer(model, fed, store, s_bound, telemetry)
    for _ in range(rounds):
        tr.run_round()
    if s_bound is not None:
        tr.runner.flush()
    return tr


# ----------------------------------------------------------------------
# The invariant: tracing on == tracing off, bit for bit
# ----------------------------------------------------------------------

@pytest.mark.parametrize("store", ["replicated", "sharded", "host"])
@pytest.mark.parametrize("s_bound", [None, 0, 1],
                         ids=["sync", "asyncS0", "asyncS1"])
def test_telemetry_is_bitwise_invisible(model, tiny_federation, tmp_path,
                                        store, s_bound):
    """Same store policy, same engine mode: the traced run's parameter
    trajectory, WAN ledger and trace count must equal the untraced run's
    exactly -- telemetry lives entirely outside jit and outside the RNG
    draw order."""
    off = _run(model, tiny_federation, store, s_bound, None)
    tel = Telemetry(str(tmp_path / "t"))
    on = _run(model, tiny_federation, store, s_bound, tel)

    for a, b in zip(jax.tree.leaves(off.params), jax.tree.leaves(on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert off.comm.total_bytes == on.comm.total_bytes
    assert off.engine.num_round_traces == on.engine.num_round_traces == 1
    # tracing adds ZERO retraces: every logged trace is an initial compile
    assert all(t["reason"] == "initial" for t in on.engine.trace_log)
    # and the artifacts actually materialized on the traced side
    paths = tel.flush()
    validate_events(load_jsonl(paths["events_jsonl"]))


def test_telemetry_defaults_to_noop_stubs(model, tiny_federation):
    """telemetry=None threads the shared NULL_TELEMETRY singleton through
    engine and store -- the off path allocates nothing per round."""
    tr = _trainer(model, tiny_federation, "replicated", None, None)
    assert tr.engine.telemetry is NULL_TELEMETRY
    assert tr.engine.store.telemetry is NULL_TELEMETRY
    assert not NULL_TELEMETRY.enabled
    sp = NULL_TELEMETRY.span("round", anything=1)
    with sp as s:
        assert s.set(x=2) is s and s.sync_on(object()) is s
    assert NULL_TELEMETRY.flush() == {}


# ----------------------------------------------------------------------
# Span stream: schema, nesting, taxonomy, zero-retrace
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_sync(model, tiny_federation, tmp_path_factory):
    tel = Telemetry(str(tmp_path_factory.mktemp("obs") / "sync"))
    tr = _run(model, tiny_federation, "replicated", None, tel, rounds=3)
    return tr, tel, tel.flush()


@pytest.fixture(scope="module")
def traced_async(model, tiny_federation, tmp_path_factory):
    tel = Telemetry(str(tmp_path_factory.mktemp("obs") / "async"))
    tr = _run(model, tiny_federation, "replicated", 1, tel, rounds=3)
    return tr, tel, tel.flush()


def test_jsonl_schema_and_nesting(traced_sync):
    tr, tel, paths = traced_sync
    events = load_jsonl(paths["events_jsonl"])
    validate_events(events)          # keys, schema version, parent nesting
    assert events and all(e["schema"] == SCHEMA_VERSION for e in events)
    names = {e["name"] for e in events}
    assert {"round", "pack", "reschedule", "store_stream",
            "round_program"} <= names
    # one round span per round, each a root (no parent)
    rounds = [e for e in events if e["name"] == "round"]
    assert len(rounds) == 3
    assert all(e["parent"] is None for e in rounds)
    # pack/round_program spans nest under a round span
    rids = {e["id"] for e in rounds}
    for e in events:
        if e["name"] in ("pack", "round_program"):
            assert e["parent"] in rids


def test_round_path_spans_and_host_reads(traced_sync):
    """The sync round's span tree, and its device->host reads: the Alg. 3
    picks under ``reschedule``, one Fig. 7 KLD per mediator under
    ``schedule_stats``. Each round span counts them, and the registry
    mirrors the total."""
    _, tel, _ = traced_sync
    events = tel.tracer.events
    by_id = {e["id"]: e for e in events}
    m = math.ceil(C / GAMMA)
    # the trainer reschedules in its first round only
    for name, parent, n in [("round_keys", "round", 3),
                            ("round_program", "round", 3),
                            ("reschedule", "round", 1), ("pack", "round", 1),
                            ("host_read.alg3_picks", "reschedule", 1),
                            ("schedule_stats", "reschedule", 1),
                            ("host_read.kld", "schedule_stats", m),
                            ("store_stream", "pack", 1)]:
        got = [by_id[e["parent"]]["name"] for e in events
               if e["name"] == name]
        assert got == [parent] * n, name
    rounds = [e for e in events if e["name"] == "round"]
    assert [e["attrs"]["host_reads"] for e in rounds] == [1 + m, 0, 0]
    assert tel.host_reads == 1 + m
    assert tel.metrics.snapshot()["astraea_host_reads_total"] == 1 + m
    # it reschedules once, so no schedule is built ahead
    assert tel.metrics.snapshot()["astraea_schedule_ahead_total"] == 0


def test_host_read_is_the_plain_read_counted_when_recording():
    counts = np.random.default_rng(0).integers(0, 9, (8, 5)).astype(float)
    tel = Telemetry()
    x = jax.numpy.arange(4.0) / 3
    np.testing.assert_array_equal(host_read(tel, "x", np.asarray, x),
                                  np.asarray(x))
    assert host_read(None, "x", float, x[1]) == float(x[1])
    assert tel.host_reads == 1 and NULL_TELEMETRY.host_reads == 0
    # the loop oracle reads one score vector per greedy step
    loop = scheduling.reschedule(counts, 3, impl="loop", telemetry=tel)
    assert [m.clients for m in loop] == [
        m.clients for m in scheduling.reschedule(counts, 3, impl="loop")]
    assert tel.host_reads == 1 + 8
    assert [e["name"] for e in tel.tracer.events].count(
        "host_read.alg3_scores") == 8


def _host_warp_count(eng, round_idx):
    """Masked-in slots drawn as augmented in round ``round_idx`` of the
    engine's held schedule, redrawn outside the round program."""
    from repro.core import augmentation
    data_args, plan_args, _, slot, row_to_group, m_real = eng._schedule
    xs, ys, ms = eng.store.slot_data(data_args, plan_args)
    ms = ms * slot[..., None]
    keys = eng._round_keys(row_to_group, m_real, round_idx)
    total = 0
    for r in range(xs.shape[0]):
        aks = jax.random.split(
            jax.random.fold_in(keys[r], augmentation.AUG_SALT), xs.shape[1])
        for c in range(xs.shape[1]):
            d = augmentation.online_draw(aks[c], xs[r, c], ys[r, c],
                                         ms[r, c], eng._aug_plan)
            total += int(np.sum(np.asarray(d.is_aug)
                                & (np.asarray(ms[r, c]) > 0)))
    return total


def test_warp_gauges_publish_the_rounds_warp(model, tiny_federation):
    """A recording Telemetry publishes the round's warped-slot count and
    the capacity that warped them; the count is the host's recount of
    ``is_aug & mask``. Reading them adds no round-path host read."""
    def run(alpha, telemetry):
        tr = AstraeaTrainer(model, adam(1e-3), tiny_federation,
                            clients_per_round=C, gamma=GAMMA,
                            local=LocalSpec(10, EM), alpha=alpha, seed=0,
                            mesh=make_mediator_mesh(1), telemetry=telemetry)
        seen = []
        for r in range(2):
            tr.run_round()
            snap = telemetry.metrics.snapshot()
            seen.append((snap.get("astraea_warp_slots"),
                         snap.get("astraea_warp_capacity")))
            if alpha is not None:
                assert seen[-1][0] == _host_warp_count(tr.engine, r)
        reads = [e["attrs"]["host_reads"] for e in telemetry.tracer.events
                 if e["name"] == "round"]
        return seen, reads

    seen, reads = run(0.67, Telemetry())
    assert all(0 < slots <= cap for slots, cap in seen)
    seen_off, reads_off = run(None, Telemetry())
    assert seen_off == [(None, None)] * 2       # no Alg. 2, no gauges
    assert reads == reads_off


def test_null_telemetry_never_reads_the_warp_count():
    class Engine:
        @property
        def last_warp_slots(self):
            raise AssertionError("NullTelemetry read the warp count")

        last_warp_capacity = last_warp_slots

    assert NULL_TELEMETRY.observe_round(Engine(), duration_s=1.0) is None


def test_round_traces_stay_one_under_tracing(traced_sync):
    tr, _, _ = traced_sync
    assert tr.engine.num_round_traces == 1
    assert tr.engine.trace_log == [{"fn": "round_fn", "round": 0,
                                    "trace_index": 1, "reason": "initial"}]


def test_chrome_trace_is_perfetto_loadable(traced_sync):
    _, tel, paths = traced_sync
    with open(paths["trace_json"]) as f:
        chrome = json.load(f)
    assert isinstance(chrome["traceEvents"], list)
    assert len(chrome["traceEvents"]) == len(tel.tracer.events)
    for ev in chrome["traceEvents"]:
        assert ev["ph"] in ("X", "i")
        assert ev["ts"] >= 0 and ev["dur"] >= 0


def test_wave_charges_sum_to_round_formula(traced_async):
    """Async S=1: every round span's wan_bytes equals the sum of its wave
    spans' charges AND the paper's per-round formula
    2|w|(c E_m + ceil(c/gamma)) -- the spans are the ledger, re-keyed."""
    tr, tel, _ = traced_async
    events = tel.tracer.events
    w = count_params(tr.params) * 4
    per_round = 2 * w * (C * EM + math.ceil(C / GAMMA))
    rounds = [e for e in events if e["name"] == "round"]
    assert len(rounds) == 3
    for rspan in rounds:
        waves = [e for e in events
                 if e["name"] == "wave" and e["parent"] == rspan["id"]]
        assert waves, "async rounds execute at least one wave"
        wave_sum = sum(e["attrs"]["wan_bytes"] for e in waves)
        assert wave_sum == rspan["attrs"]["wan_bytes"] == per_round
    assert sum(e["attrs"]["wan_bytes"] for e in rounds) == \
        tr.comm.total_bytes
    # commits carry the staleness the histogram absorbed
    commits = [e for e in events if e["name"] == "commit"]
    assert commits and all(e["attrs"]["staleness_max"] <= 1 for e in commits)


# ----------------------------------------------------------------------
# Metrics registry: ledgers mirrored exactly, Prometheus + endpoint
# ----------------------------------------------------------------------

def test_prometheus_wan_equals_comm_ledger(traced_sync):
    tr, tel, paths = traced_sync
    prom = tel.metrics.to_prometheus()
    sample = {line.split()[0]: float(line.split()[1])
              for line in prom.splitlines() if not line.startswith("#")
              and "{" not in line}
    assert sample["astraea_wan_bytes_total"] == tr.comm.total_bytes
    assert sample["astraea_rounds_total"] == 3
    assert sample["astraea_round_traces"] == 1
    assert sample["astraea_unexpected_retraces"] == 0
    with open(paths["metrics_prom"]) as f:
        assert f.read() == prom


def test_metrics_jsonl_has_one_row_per_round(traced_sync):
    _, tel, paths = traced_sync
    rows = load_jsonl(paths["metrics_jsonl"])
    assert [r["round"] for r in rows] == [1, 2, 3]
    # cumulative counters never decrease across the round timeline
    for a, b in zip(rows, rows[1:]):
        assert b["astraea_wan_bytes_total"] >= a["astraea_wan_bytes_total"]


def test_staleness_histogram_absorbed(traced_async):
    tr, tel, _ = traced_async
    snap = tel.metrics.snapshot()
    hist = snap["astraea_staleness"]
    total_contrib = sum(len(e["staleness"]) for e in tr.runner.commit_log)
    assert hist["count"] == total_contrib
    assert hist["le_inf"] == total_contrib
    assert snap["astraea_commits_total"] == tr.runner.num_commits


def test_metrics_endpoint_scrape(traced_sync):
    """A live GET /metrics serves the registry's exposition with the
    Prometheus content type; other paths 404."""
    tr, tel, _ = traced_sync
    with MetricsServer(tel.metrics) as srv:
        resp = urllib.request.urlopen(srv.url, timeout=10)
        assert resp.headers["Content-Type"] == CONTENT_TYPE
        body = resp.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/other", timeout=10)
    wan = [l for l in body.splitlines()
           if l.startswith("astraea_wan_bytes_total ")]
    assert wan and float(wan[0].split()[1]) == tr.comm.total_bytes


# ----------------------------------------------------------------------
# Unified ClientStore.stats() schema (satellite)
# ----------------------------------------------------------------------

def test_store_stats_schema_is_policy_invariant(model, tiny_federation):
    """Every placement policy answers stats() with the same key set --
    the registry mirrors them without per-policy branching."""
    key_sets = {}
    for store in ("replicated", "sharded", "host"):
        tr = _trainer(model, tiny_federation, store, None, None)
        stats = tr.engine.store.stats()
        assert stats["policy"] == store
        key_sets[store] = frozenset(stats)
    assert len(set(key_sets.values())) == 1, key_sets


# ----------------------------------------------------------------------
# Unit coverage: tracer + registry primitives
# ----------------------------------------------------------------------

def test_tracer_deterministic_with_fake_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("round", round=0) as r:
        tr.instant("charge", bytes=8)
        with tr.span("pack") as p:
            p.set(m_pad=4)
    validate_events(tr.events)
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["pack"]["parent"] == r.span_id
    assert by_name["charge"]["parent"] == r.span_id
    assert by_name["pack"]["attrs"] == {"m_pad": 4}
    assert by_name["round"]["dur_us"] > by_name["pack"]["dur_us"]


def test_validate_events_rejects_escaped_child():
    bad = [
        {"schema": SCHEMA_VERSION, "kind": "span", "id": 0, "parent": None,
         "name": "round", "ts_us": 0.0, "dur_us": 10.0, "attrs": {}},
        {"schema": SCHEMA_VERSION, "kind": "span", "id": 1, "parent": 0,
         "name": "pack", "ts_us": 5.0, "dur_us": 10.0, "attrs": {}},
    ]
    with pytest.raises(ValueError, match="escapes parent"):
        validate_events(bad)
    with pytest.raises(ValueError, match="missing keys"):
        validate_events([{"schema": SCHEMA_VERSION}])


def test_counter_set_total_is_monotone():
    c = Counter("x", "")
    c.set_total(10)
    c.inc(5)
    assert c.sample() == 15
    with pytest.raises(ValueError):
        c.set_total(3)


def test_histogram_buckets_are_cumulative():
    h = Histogram("h", buckets=(1, 2, 4))
    for v in (0.5, 1.5, 3, 100):
        h.observe(v)
    s = h.sample()
    assert (s["le_1"], s["le_2"], s["le_4"], s["le_inf"]) == (1, 2, 3, 4)
    assert s["count"] == 4 and s["sum"] == pytest.approx(105.0)


def test_prometheus_exposition_shape():
    reg = MetricsRegistry()
    reg.counter("astraea_wan_bytes_total", "wan").set_total(1024)
    reg.histogram("astraea_staleness", (0, 1)).observe(1)
    text = reg.to_prometheus()
    assert "# TYPE astraea_wan_bytes_total counter" in text
    assert "astraea_wan_bytes_total 1024" in text
    assert 'astraea_staleness_bucket{le="+Inf"} 1' in text
    assert "astraea_staleness_count 1" in text
    with pytest.raises(TypeError):
        reg.gauge("astraea_wan_bytes_total")   # kind collision
