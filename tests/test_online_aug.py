"""Online rebalancing inside the round program (the PR-4 data-flow flip).

Contract under test (core/engine.py §7 + core/augmentation.py):

* stores keep the RAW federation -- per-device bytes equal the
  no-augmentation pack under every placement policy;
* all three stores produce bitwise-identical trajectories with the
  in-round resample+warp enabled, and the round executable still compiles
  exactly once (``num_round_traces == 1``), including across async waves;
* Alg. 3 schedules on the expected post-augmentation histograms and Eq. 6
  weighs mediators by expected post-augmentation sizes;
* the trainer API: ``aug_mode`` selects online / materialized / none.

The 4-device subprocess mirrors tests/test_client_store.py: device count
must be forced before jax initializes.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import LocalSpec, augmentation
from repro.core.astraea import AstraeaTrainer
from repro.core.engine import EngineConfig, FLRoundEngine
from repro.core.fedavg import FedAvgTrainer
from repro.launch.mesh import make_mediator_mesh
from repro.models.cnn import emnist_cnn
from repro.optim import adam

STORES = ("replicated", "sharded", "host")


@pytest.fixture(scope="module")
def model(tiny_federation):
    return emnist_cnn(tiny_federation.num_classes, image_size=16)


def _params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _trainer(model, fed, store="replicated", **kw):
    kw.setdefault("alpha", 0.67)
    return AstraeaTrainer(model, adam(1e-3), fed, clients_per_round=6,
                          gamma=3, local=LocalSpec(10, 1), seed=0,
                          store=store, mesh=make_mediator_mesh(1),
                          reschedule_every_round=True, **kw)


def test_online_store_bytes_stay_raw(model, tiny_federation):
    """The headline of the data-flow inversion: with online augmentation
    the per-device client-store bytes equal the raw pre-augmentation pack
    under all three placement policies; materializing inflates them."""
    for store in STORES:
        on = _trainer(model, tiny_federation, store)
        raw = _trainer(model, tiny_federation, store, alpha=None)
        assert on.engine.store.per_device_bytes() == \
            raw.engine.store.per_device_bytes(), store
        assert on.engine.store.stats()["policy"] == store
    mat = _trainer(model, tiny_federation, aug_mode="materialized")
    rawb = _trainer(model, tiny_federation, alpha=None
                    ).engine.store.per_device_bytes()
    assert mat.engine.store.per_device_bytes() > rawb
    assert mat.extra_storage_frac > 0
    # the online trainer reports the avoided cost and realizes none of it
    on = _trainer(model, tiny_federation)
    assert on.extra_storage_frac == 0.0
    assert on.planned_extra_frac == pytest.approx(mat.extra_storage_frac)


def test_online_stores_bitwise_identical_single_trace(model, tiny_federation):
    """sharded + host == replicated bitwise with the in-round warp on, and
    per-round reschedules never re-trace the augmented round executable."""
    runs = {}
    for store in STORES:
        tr = _trainer(model, tiny_federation, store)
        tr.run_round()
        tr.run_round()
        runs[store] = tr
        assert tr.engine.num_round_traces == 1, store
        assert tr.engine.num_schedule_packs == 2
    for store in ("sharded", "host"):
        _params_equal(runs["replicated"].params, runs[store].params)


def test_online_differs_from_no_aug(model, tiny_federation):
    """The in-round warp must actually change training (guards against the
    hook silently not running)."""
    on = _trainer(model, tiny_federation)
    off = _trainer(model, tiny_federation, alpha=None)
    on.run_round()
    off.run_round()
    same = all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree.leaves(on.params), jax.tree.leaves(off.params)))
    assert not same


def test_online_schedule_uses_expected_counts(model, tiny_federation):
    """Alg. 3 packs mediators by the histograms clients will actually train
    on: raw counts scaled by (1 + plan)."""
    tr = _trainer(model, tiny_federation)
    plan = tr.augmentation_plan
    raw = tiny_federation.client_counts()
    np.testing.assert_allclose(tr.engine._counts, raw * (1.0 + plan))
    # and the engine refuses a plan that does not match the class count
    with pytest.raises(ValueError, match="aug_plan shape"):
        FLRoundEngine(model, adam(1e-3), tiny_federation,
                      EngineConfig.astraea(clients_per_round=6, gamma=3,
                                           local=LocalSpec(10, 1)),
                      mesh=make_mediator_mesh(1),
                      aug_plan=np.zeros(3, np.int64))


def test_zero_plan_disables_engine_hook(model, tiny_federation):
    """A perfectly balanced federation yields an all-zero plan: there is
    nothing to augment, so online mode must NOT install the in-round
    resample (which would bootstrap-resample every batch and pay a warp it
    discards) -- the trajectory stays bitwise-identical to alpha=None."""
    from repro.data.federated import FederatedDataset
    rng = np.random.default_rng(0)
    nc = tiny_federation.num_classes
    imgs = [rng.normal(size=(nc * 4, 16, 16, 1)).astype(np.float32)
            for _ in range(6)]
    labels = [np.tile(np.arange(nc), 4).astype(np.int64) for _ in range(6)]
    fed = FederatedDataset(imgs, labels, tiny_federation.test_images,
                           tiny_federation.test_labels, nc, "balanced")
    kw = dict(clients_per_round=4, gamma=2, local=LocalSpec(8, 1), seed=0,
              mesh=make_mediator_mesh(1))
    on = AstraeaTrainer(model, adam(1e-3), fed, alpha=0.67, **kw)
    assert on.augmentation_plan is not None
    assert np.all(on.augmentation_plan == 0)
    assert on.engine._aug_plan is None          # hook not installed
    off = AstraeaTrainer(model, adam(1e-3), fed, alpha=None, **kw)
    on.run_round()
    off.run_round()
    _params_equal(on.params, off.params)
    assert on.comm.total_bytes == off.comm.total_bytes  # no plan broadcast


def test_online_async_s0_bitwise_and_single_trace(model, tiny_federation):
    """S=0 async == synchronous engine bitwise WITH augmentation enabled
    (aug keys ride the round keys, not wave membership), still one trace."""
    from repro.core.async_engine import AsyncSpec
    from repro.core.staleness import StragglerSpec
    sync = _trainer(model, tiny_federation)
    asy = _trainer(model, tiny_federation,
                   async_spec=AsyncSpec(
                       staleness_bound=0, wave_size=1,
                       straggler=StragglerSpec(model="fixed", seed=0)))
    for _ in range(2):
        sync.run_round()
        asy.run_round()
    _params_equal(sync.params, asy.params)
    assert asy.engine.num_round_traces == 1


def test_trainer_aug_mode_api(model, tiny_federation):
    """aug_mode plumbing + the dataclasses.replace dataset rebuild."""
    with pytest.raises(ValueError, match="aug_mode"):
        _trainer(model, tiny_federation, aug_mode="lazy")
    # alpha=None disables augmentation regardless of aug_mode
    off = _trainer(model, tiny_federation, alpha=None, aug_mode="online")
    assert off.augmentation_plan is None
    assert off.engine._aug_plan is None
    on = _trainer(model, tiny_federation)
    assert on.engine._aug_plan is not None
    assert on.augmentation_plan.shape == (tiny_federation.num_classes,)
    # the materialized rebuild preserves every non-client field (the old
    # positional construction broke as soon as FederatedDataset grew one)
    mat = _trainer(model, tiny_federation, aug_mode="materialized")
    assert mat.data.name == tiny_federation.name
    assert mat.data.num_classes == tiny_federation.num_classes
    np.testing.assert_array_equal(mat.data.test_images,
                                  tiny_federation.test_images)
    np.testing.assert_array_equal(mat.data.test_labels,
                                  tiny_federation.test_labels)
    assert mat.engine._aug_plan is None         # oracle mode: host phase


def test_fedavg_online_aug(model, tiny_federation):
    """The aug-only ablation through FedAvgTrainer: plan wired, store raw,
    single trace over per-round random reschedules."""
    fa = FedAvgTrainer(model, adam(1e-3), tiny_federation,
                       clients_per_round=4, local=LocalSpec(10, 1),
                       alpha=0.67, seed=0, mesh=make_mediator_mesh(1))
    raw = FedAvgTrainer(model, adam(1e-3), tiny_federation,
                        clients_per_round=4, local=LocalSpec(10, 1),
                        seed=0, mesh=make_mediator_mesh(1))
    assert fa.engine._aug_plan is not None
    assert fa.engine.store.per_device_bytes() == \
        raw.engine.store.per_device_bytes()
    fa.run_round()
    fa.run_round()
    assert fa.engine.num_round_traces == 1
    with pytest.raises(ValueError, match="aug_mode"):
        FedAvgTrainer(model, adam(1e-3), tiny_federation, clients_per_round=4,
                      local=LocalSpec(10, 1), alpha=0.5, aug_mode="eager")


def test_adaptive_plan_refreshes_per_reschedule(model, tiny_federation):
    """Per-round adaptive rebalancing (PR-4 follow-up): the Alg. 2 plan is
    recomputed from the selected cohort's label histograms at every
    reschedule, re-broadcast to the cohort (metered), handed to the
    round as an operand -- and the one compiled executable is reused."""
    c = 6
    tr = _trainer(model, tiny_federation, adaptive_plan=True)
    eng = tr.engine
    k = tiny_federation.num_clients
    nc = tiny_federation.num_classes
    # init: the global plan broadcast to every client
    assert eng.comm.total_bytes == nc * 4 * k
    plans = []
    for r in range(3):
        tr.run_round()
        assert eng.last_plan is not None and eng.last_plan.shape == (nc,)
        plans.append(eng.last_plan.copy())
        # Alg. 3 packs by the cohort plan's expected post-aug histograms
        np.testing.assert_allclose(
            eng._counts, tiny_federation.client_counts()
            * (1.0 + eng.last_plan.astype(np.float64)))
        # each reschedule re-broadcast the plan to its c-client cohort,
        # on top of the §IV-C per-round model legs
        from repro.models.cnn import count_params
        w = count_params(tr.params) * 4
        round_bytes = 2 * w * (c * 1 + -(-c // 3))      # E_m=1, gamma=3
        assert eng.comm.total_bytes == pytest.approx(
            nc * 4 * (k + (r + 1) * c) + (r + 1) * round_bytes)
    # the cohorts differ, so at least one refreshed plan must differ from
    # the initial global plan (seeded selection; holds for this federation)
    assert any(not np.array_equal(p, tr.augmentation_plan) for p in plans)
    # operand swap, not re-trace: still exactly one compiled round
    assert eng.num_round_traces == 1
    assert eng.num_schedule_packs == 3


def test_adaptive_plan_changes_training_vs_static(model, tiny_federation):
    """The refreshed cohort plans must actually reach the in-round hook:
    an adaptive run diverges from the static-plan run once a cohort's
    histogram differs from the global one."""
    static = _trainer(model, tiny_federation)
    adapt = _trainer(model, tiny_federation, adaptive_plan=True)
    diverged = False
    for _ in range(3):
        static.run_round()
        adapt.run_round()
        if not np.array_equal(adapt.engine.last_plan,
                              static.augmentation_plan):
            diverged = True
    assert diverged
    same = all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree.leaves(static.params), jax.tree.leaves(adapt.params)))
    assert not same


def test_adaptive_plan_validation(model, tiny_federation):
    """adaptive_plan needs the online pipeline (alpha set, online mode);
    the engine refuses adaptivity without an installed hook."""
    with pytest.raises(ValueError, match="adaptive_plan"):
        _trainer(model, tiny_federation, adaptive_plan=True, alpha=None)
    with pytest.raises(ValueError, match="adaptive_plan"):
        _trainer(model, tiny_federation, adaptive_plan=True,
                 aug_mode="materialized")
    with pytest.raises(ValueError, match="adaptive_aug_alpha"):
        FLRoundEngine(model, adam(1e-3), tiny_federation,
                      EngineConfig.astraea(clients_per_round=6, gamma=3,
                                           local=LocalSpec(10, 1)),
                      mesh=make_mediator_mesh(1), adaptive_aug_alpha=0.67)


def test_adaptive_plan_installs_hook_on_balanced_data(model, tiny_federation):
    """A balanced federation yields an all-zero initial plan; adaptive mode
    must still install the in-round hook (a later cohort may drift),
    unlike the static zero-plan fast path."""
    from repro.data.federated import FederatedDataset
    rng = np.random.default_rng(0)
    nc = tiny_federation.num_classes
    imgs = [rng.normal(size=(nc * 4, 16, 16, 1)).astype(np.float32)
            for _ in range(6)]
    labels = [np.tile(np.arange(nc), 4).astype(np.int64) for _ in range(6)]
    fed = FederatedDataset(imgs, labels, tiny_federation.test_images,
                           tiny_federation.test_labels, nc, "balanced")
    kw = dict(clients_per_round=4, gamma=2, local=LocalSpec(8, 1), seed=0,
              mesh=make_mediator_mesh(1))
    tr = AstraeaTrainer(model, adam(1e-3), fed, alpha=0.67,
                        adaptive_plan=True, **kw)
    assert np.all(tr.augmentation_plan == 0)
    assert tr.engine._aug_plan is not None      # hook installed anyway
    tr.run_round()
    assert tr.engine.num_round_traces == 1


def test_eq6_weights_are_expected_post_aug_sizes(model, tiny_federation):
    """With the plan on, a mediator's Eq. 6 weight becomes
    sum(mask * (1 + plan[y])) over its clients -- the *expected
    post-augmentation* size, exactly sum_c counts_kc (1 + plan_c).  The
    replicated store's plan args expose the (M_pad, gamma) gather ids, so
    the expectation is reconstructible host-side."""
    tr = _trainer(model, tiny_federation)
    eng = tr.engine
    data_args, plan_args, unperm, slot, row_to_group, m_real = \
        eng.ensure_schedule()
    keys = eng._round_keys(row_to_group, m_real)
    _, weights = eng.wave_fn(eng.params, data_args, plan_args, unperm, slot,
                             keys, *eng.aug_args())
    weights = np.asarray(weights)
    idx = np.asarray(plan_args[0])              # replicated store gather ids
    slot_np = np.asarray(slot)
    plan = tr.augmentation_plan
    per_client = (tiny_federation.client_counts() * (1.0 + plan)).sum(axis=1)
    expect = (slot_np * per_client[idx]).sum(axis=1)
    np.testing.assert_allclose(weights, expect, rtol=1e-5)
    assert np.all(weights[np.asarray(row_to_group) < 0] == 0)  # dummy rows


def _fedavg_trainer(model, fed):
    return FedAvgTrainer(model, adam(1e-3), fed, clients_per_round=4,
                         local=LocalSpec(10, 1), alpha=0.67, seed=0,
                         mesh=make_mediator_mesh(1))


@pytest.mark.parametrize("make", [_trainer, _fedavg_trainer],
                         ids=["astraea", "fedavg"])
def test_compacted_warp_keeps_the_trajectory(model, tiny_federation,
                                             monkeypatch, make):
    """Warping only the masked-in augmented slots, at a rung below the
    slot count, trains exactly as warping at the top rung, which does a
    full warp's work: the parameters after 2 rounds are bitwise equal,
    through the mediator chain and the parallel-clients (FedAvg + Alg. 2)
    path, and each round program is traced once."""
    runs = {}
    for ladder in ("full", "compacted"):
        with monkeypatch.context() as mp:
            if ladder == "full":
                mp.setattr(augmentation, "warp_ladder", lambda n: (n,))
            else:   # small rungs, so this tiny federation climbs a ladder
                mp.setattr(augmentation, "LADDER_BASE", 8)
            tr = make(model, tiny_federation)
            tr.run_round()
            tr.run_round()
        eng = tr.engine
        assert eng.num_round_traces == 1
        runs[ladder] = (tr.params, int(eng.last_warp_slots),
                        int(eng.last_warp_capacity))
    (full, n_full, cap_full), (comp, n_comp, cap_comp) = \
        runs["full"], runs["compacted"]
    _params_equal(full, comp)
    assert n_full == n_comp > 0
    assert n_comp <= cap_comp < cap_full


_MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax
    import numpy as np
    from repro.core import LocalSpec
    from repro.core.astraea import AstraeaTrainer
    from repro.core.async_engine import AsyncSpec
    from repro.core.staleness import StragglerSpec
    from repro.data.federated import partition, EMNIST_LIKE
    from repro.launch.mesh import make_mediator_mesh
    from repro.models.cnn import emnist_cnn
    from repro.optim import adam

    spec = dataclasses.replace(EMNIST_LIKE, num_classes=8, image_size=16)
    fed = partition(spec, num_clients=12, total_samples=600, test_samples=160,
                    sizes="instagram", global_dist="letterfreq",
                    local="random", seed=0, name="tiny")
    model = emnist_cnn(8, image_size=16)

    def run(store, alpha=0.67, async_spec=None):
        tr = AstraeaTrainer(model, adam(1e-3), fed, clients_per_round=6,
                            gamma=3, local=LocalSpec(10, 1), alpha=alpha,
                            seed=0, store=store, pad_mediators_to=4,
                            reschedule_every_round=True,
                            async_spec=async_spec,
                            mesh=make_mediator_mesh(4))
        tr.run_round()
        tr.run_round()
        return tr

    def check(a, b):
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    # (1) 4-device mesh: all three stores bitwise identical with the
    # in-round resample+warp enabled
    r4, s4, h4 = run("replicated"), run("sharded"), run("host")
    check(s4, r4)
    check(h4, r4)

    # (2) one trace each, augmentation on, across per-round reschedules
    for tr in (r4, s4, h4):
        assert tr.engine.num_round_traces == 1, tr.engine.num_round_traces
        assert tr.engine.num_schedule_packs == 2

    # (3) per-device store bytes equal the raw pack (no aug) per policy
    for store, tr in (("replicated", r4), ("sharded", s4), ("host", h4)):
        raw = run(store, alpha=None)
        assert tr.engine.store.per_device_bytes() == \\
            raw.engine.store.per_device_bytes(), store

    # (4) async waves on the 4-device mesh: S=0 == sync bitwise with aug,
    # still one trace
    a4 = run("replicated", async_spec=AsyncSpec(
        staleness_bound=0, wave_size=1,
        straggler=StragglerSpec(model="fixed", seed=0)))
    check(a4, r4)
    assert a4.engine.num_round_traces == 1
    print("OK")
""")


def test_online_aug_multi_device(tmp_path):
    """The acceptance claims on a real 4-device mesh (subprocess: the
    device count must be forced before jax initializes)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "OK" in proc.stdout
