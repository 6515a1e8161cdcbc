"""The one-round schedule lookahead of ``FLRoundEngine.run_round``.

When rescheduling every round, ``run_round`` draws the next round's
cohort and enqueues its Alg. 3 and Fig. 7 in one device launch before
dispatching the current round, and reads them once the current round is
queued. These tests hold it to the schedule built on the spot: a
test-local round runs the engine's own pieces in the order the round
ran before the lookahead (draw, Alg. 3 and Fig. 7, pack, keys,
program), and every parameter, schedule, statistic and ledger must match
round by round. They also pin the mechanism: the next round's reads,
made after the round's program is queued, and the
``astraea_schedule_ahead_total`` counter.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalSpec, augmentation, scheduling
from repro.core import distribution as dist
from repro.core.async_engine import AsyncRoundEngine, AsyncSpec
from repro.core.engine import EngineConfig, FLRoundEngine
from repro.data.synthetic import federation_counts
from repro.launch.mesh import make_mediator_mesh
from repro.models.cnn import emnist_cnn
from repro.obs import Telemetry
from repro.optim import adam

ROUNDS = 3


@pytest.fixture(scope="module")
def model(tiny_federation):
    return emnist_cnn(tiny_federation.num_classes, image_size=16)


def _astraea(**kw):
    kw.setdefault("reschedule_every_round", True)
    return EngineConfig.astraea(clients_per_round=6, gamma=3,
                                local=LocalSpec(10, 1), seed=0,
                                pad_mediators_to=2, donate_params=False,
                                **kw)


def _fedavg(**kw):
    return EngineConfig.fedavg(clients_per_round=4, local=LocalSpec(10, 1),
                               seed=0, pad_mediators_to=4,
                               donate_params=False, **kw)


def _engine(model, fed, cfg, aug=None, telemetry=None):
    """``aug``: None (no Alg. 2), "static" or "adaptive" online plan."""
    plan = alpha = None
    if aug is not None:
        phase = augmentation.resolve_aug_mode(fed, 0.67, "online", 0)
        plan, alpha = augmentation.resolve_engine_plan(
            phase, aug == "adaptive", 0.67)
        fed = phase.data
    return FLRoundEngine(model, adam(1e-3), fed, cfg,
                         mesh=make_mediator_mesh(1), aug_plan=plan,
                         adaptive_aug_alpha=alpha, telemetry=telemetry)


def _on_the_spot_round(eng):
    """One sync round as it ran before the lookahead: the schedule built
    at the round's start, then keys and the round program."""
    assert eng._ahead is None
    data_args, plan_args, unperm, slot, rtg, m_real = eng.ensure_schedule()
    keys = eng._round_keys(rtg, m_real)
    eng.server_state = eng._round_fn(eng.server_state, data_args, plan_args,
                                     unperm, slot, keys, *eng.extra_args())
    c = min(eng.cfg.clients_per_round, eng.data.num_clients)
    if eng.cfg.aggregate == "weights":
        eng.comm.fedavg_round(c)
    else:
        eng.comm.astraea_round(c, eng.cfg.gamma, eng.cfg.mediator_epochs)
    if eng.store.exchange_bytes_per_round:
        eng.comm.store_exchange(eng.store.exchange_bytes_per_round)
    eng.comm.end_round()
    eng._round += 1


def _per_mediator_stats(eng):
    """Fig. 7 as the engine computed it before the fused read: one
    ``kld_to_uniform`` of each mediator's summed histogram."""
    if eng.cfg.schedule == "random" and eng.cfg.gamma == 1:
        return None
    klds = np.array([float(dist.kld_to_uniform(jnp.asarray(
        eng._counts[g].sum(0)))) for g in eng.last_groups])
    return {"kld_mean": klds.mean(), "kld_median": np.median(klds),
            "kld_max": klds.max(), "kld_min": klds.min(),
            "num_mediators": len(klds)}


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_same_round(ahead, spot):
    """Everything a round leaves behind, ``ahead`` against ``spot``."""
    _assert_same(ahead.server_state, spot.server_state)
    assert ahead.last_groups == spot.last_groups
    _assert_same(ahead._schedule, spot._schedule)
    assert ahead.comm.ledger_totals() == spot.comm.ledger_totals()
    assert ahead.comm.total_bytes == spot.comm.total_bytes
    if spot.last_plan is not None:
        np.testing.assert_array_equal(ahead.last_plan, spot.last_plan)
    want = _per_mediator_stats(spot)
    got = ahead.last_schedule_stats
    if want is None:
        assert got is None
        return
    assert got["num_mediators"] == want["num_mediators"]
    for key in ("kld_mean", "kld_median", "kld_max", "kld_min"):
        np.testing.assert_allclose(got[key], want[key], rtol=1e-6)
    assert {k: v for k, v in got.items() if k.startswith("store_")} == \
        {k: v for k, v in spot.last_schedule_stats.items()
         if k.startswith("store_")}


CASES = {
    "astraea": (_astraea(), None),
    "astraea-alg2": (_astraea(), "static"),
    "astraea-kernel": (_astraea(reschedule_kernel=True), None),
    "adaptive-plan": (_astraea(), "adaptive"),
    "row-exec-map": (_astraea(row_exec="map"), "static"),
    "random-gamma3": (dataclasses.replace(_astraea(), schedule="random"),
                      None),
    "sharded": (_astraea(store="sharded"), None),
    "spilled-depth1": (_astraea(store="spilled", store_prefetch_depth=1),
                       None),
    "spilled-depth2": (_astraea(store="spilled", store_prefetch_depth=2),
                       None),
    "fedavg": (_fedavg(), None),
    "fedavg-alg2": (_fedavg(), "static"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_schedule_ahead_is_bitwise_the_on_the_spot_schedule(
        model, tiny_federation, case):
    """Round by round, the lookahead engine's parameters, schedule
    (``last_groups`` and the packed ``_schedule``), Alg. 2 plan and
    ledgers are bitwise those of building each schedule at its round's
    start, and its Fig. 7 statistics are the per-mediator ones within
    1e-6 relative."""
    cfg, aug = CASES[case]
    ahead = _engine(model, tiny_federation, cfg, aug)
    spot = _engine(model, tiny_federation, cfg, aug)
    for r in range(ROUNDS):
        ahead.run_round()
        _on_the_spot_round(spot)
        _assert_same_round(ahead, spot)
        assert ahead._ahead.round == r + 1
    assert ahead.num_schedules_ahead == ROUNDS - 1
    assert ahead.num_schedule_packs == spot.num_schedule_packs == ROUNDS
    assert ahead.num_round_traces == spot.num_round_traces == 1
    if cfg.store == "spilled":
        for key in ("prefetch_hits", "prefetch_misses", "cache_hit_rows",
                    "tier_rows", "num_streams", "streamed_bytes"):
            assert ahead.store.stats()[key] == spot.store.stats()[key], key


@pytest.mark.parametrize("cfg", [_astraea(), _fedavg()],
                         ids=["astraea", "fedavg"])
def test_async_s0_matches_the_sync_lookahead(model, tiny_federation, cfg):
    """Async waves at S=0 build each schedule on the spot; the sync engine
    builds it one round ahead. The trajectories stay bitwise equal."""
    sync = _engine(model, tiny_federation, cfg)
    a = AsyncRoundEngine(_engine(model, tiny_federation, cfg),
                         AsyncSpec(staleness_bound=0, wave_size=0))
    for _ in range(ROUNDS):
        sync.run_round()
        a.run_round()
        _assert_same(sync.params, a.engine.params)
        assert sync.last_groups == a.engine.last_groups
    assert sync.num_schedules_ahead == ROUNDS - 1
    assert a.engine.num_schedules_ahead == 0


def test_the_reads_of_a_round_follow_its_round_program(model,
                                                      tiny_federation):
    """The kld schedule of the next round is read inside this round's
    span, after its ``round_program`` span: the Alg. 3 picks once, then
    each mediator's KLD once, all copies of what the one launch computed
    (the first round also reads its own schedule, on the spot, under
    ``reschedule``). No launch sits between the picks and the KLDs."""
    tel = Telemetry()
    eng = _engine(model, tiny_federation, _astraea(), "static", tel)
    for _ in range(ROUNDS):
        eng.run_round()
    events = tel.tracer.events
    by_id = {e["id"]: e for e in events}
    m = len(eng.last_groups)
    rounds = [e for e in events if e["name"] == "round"]
    assert [e["attrs"]["host_reads"] for e in rounds] == \
        [2 * (1 + m)] + [1 + m] * (ROUNDS - 1)
    for rnd in rounds:
        kids = [e for e in events if e["parent"] == rnd["id"]]
        names = [e["name"] for e in kids]
        assert names.count("reschedule") == (2 if rnd is rounds[0] else 1)
        prog = kids[names.index("round_program")]
        (read,) = [e for e in kids if e["name"] == "host_read.alg3_picks"]
        (stats,) = [e for e in kids if e["name"] == "schedule_stats"]
        assert prog["ts_us"] + prog["dur_us"] <= read["ts_us"]
        assert read["ts_us"] + read["dur_us"] <= stats["ts_us"]
        klds = [e for e in events if e["name"] == "host_read.kld"
                and e["parent"] == stats["id"]]
        assert len(klds) == m
    first = next(e for e in events if e["name"] == "reschedule")
    assert sorted(e["name"] for e in events
                  if e["parent"] == first["id"]) == \
        ["host_read.alg3_picks", "schedule_stats"]
    assert len([e for e in events if e["name"] == "host_read.kld"]) == \
        m * (ROUNDS + 1)


def test_fused_fig7_equals_the_per_mediator_kld():
    """The KLDs the Alg. 3 launch computes are ``kld_to_uniform`` of each
    mediator's summed histogram within 1e-6 relative, and the picks are
    the loop oracle's, for Alg. 3 (scan and kernel) and for the random
    grouping."""
    counts = federation_counts(23, 7, seed=4).astype(np.float64)
    loop = scheduling.reschedule(counts, 5, impl="loop")
    assert all(m.kld is None for m in loop)
    want = scheduling.schedule_stats(loop)      # one launch per mediator
    for meds in (scheduling.reschedule(counts, 5),
                 scheduling.reschedule(counts, 5, use_kernel=True)):
        assert [m.clients for m in meds] == [m.clients for m in loop]
        got = scheduling.schedule_stats(meds)
        assert got["num_mediators"] == want["num_mediators"] == 5
        for key in ("kld_mean", "kld_median", "kld_max", "kld_min"):
            np.testing.assert_allclose(got[key], want[key], rtol=1e-6)
    meds = scheduling.random_schedule(23, 5, counts, seed=3)
    assert len(meds) == 5 and len(meds[-1].clients) == 3
    for m in meds:
        np.testing.assert_array_equal(m.counts, counts[m.clients].sum(0))
        np.testing.assert_allclose(
            m.kld_to_uniform(),
            float(dist.kld_to_uniform(jnp.asarray(m.counts))), rtol=1e-6)


def test_schedule_ahead_counter_and_later_fits(model, tiny_federation,
                                               tmp_path):
    """``astraea_schedule_ahead_total`` counts rounds - 1 over a fit of n
    rounds. The schedule a finished fit leaves built but unused is the
    next round's: a checkpoint and a second fit see the cohort sequence
    of building every schedule on the spot."""
    from repro.checkpoint.msgpack_ckpt import save_trainer
    tel = Telemetry()
    eng = _engine(model, tiny_federation, _astraea(), None, tel)
    eng.fit(2, eval_every=2)
    assert tel.metrics.snapshot()["astraea_schedule_ahead_total"] == 1
    assert eng._ahead is not None and eng._ahead.round == 2
    groups = [eng.last_groups]
    save_trainer(str(tmp_path / "ckpt"), eng)
    eng.fit(2, eval_every=2)
    groups.append(eng.last_groups)
    assert tel.metrics.snapshot()["astraea_schedule_ahead_total"] == 3

    spot = _engine(model, tiny_federation, _astraea())
    want = []
    for r in range(4):
        _on_the_spot_round(spot)
        want.append(spot.last_groups)
    assert groups == [want[1], want[3]]
    _assert_same(eng.params, spot.params)


def test_a_loaded_round_rebuilds_the_schedule_ahead(model, tiny_federation,
                                                    tmp_path):
    """A checkpoint loaded between rounds moves the engine's round back;
    the schedule built ahead was for another round, so its cohort is
    rescheduled for the loaded one (the random grouping is seeded by the
    round) -- as building it on the spot would."""
    from repro.checkpoint.msgpack_ckpt import load_trainer, save_trainer
    cfg = dataclasses.replace(_astraea(), schedule="random")
    ahead = _engine(model, tiny_federation, cfg)
    spot = _engine(model, tiny_federation, cfg)
    for eng, step in ((ahead, ahead.run_round),
                      (spot, lambda: _on_the_spot_round(spot))):
        step()
        save_trainer(str(tmp_path / f"{id(eng)}"), eng)
        step()
        load_trainer(str(tmp_path / f"{id(eng)}"), eng)
        step()
    assert ahead._round == spot._round == 2
    assert ahead.last_groups == spot.last_groups
    assert ahead.num_schedules_ahead == 1
    _assert_same(ahead.params, spot.params)
