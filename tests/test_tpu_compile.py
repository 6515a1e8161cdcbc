"""The FL-path Pallas kernels compile under Mosaic for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described topology.
Interpret mode cannot see what Mosaic refuses (a block that breaks the
(8, 128) tiling rule, a rank-1 block whose layout XLA and Mosaic disagree
on, too much VMEM), so each kernel is compiled here at the widths the
engine gives it and must lower to a ``tpu_custom_call``; so must the
whole Astraea round on the 2x2 mesh with tensor-parallel rows.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a module that loads
it while being collected would break the other test workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import affine_warp as aw
from repro.kernels import fedavg_agg as fa
from repro.kernels import kld_score as kl

EMNIST_PARAMS = 68_873          # emnist_cnn(47, 28)
CINIC_PARAMS = 2_168_362        # cinic_cnn(10, 32)
K, C, GAMMA = 500, 47, 5        # the 500-client, 47-class federation


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_mosaic(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,c", [(28, 1), (32, 3)])
def test_affine_warp_compiles(one_chip, h, c):
    b = 64
    _assert_mosaic(lambda i, m, t: aw.affine_warp(i, m, t, interpret=False),
                   _s(one_chip, (b, h, h, c)), _s(one_chip, (b, 2, 2)),
                   _s(one_chip, (b, 2)))


@pytest.mark.parametrize("h,c", [(28, 1), (32, 3)])
def test_compact_warp_compiles(one_chip, monkeypatch, h, c):
    """The engine's compacted warp: a ``lax.switch`` over the ladder's
    rungs, one Mosaic kernel each."""
    from repro.core import augmentation
    from repro.kernels import ops
    n = 5000                                    # rungs 1024, 4096, 5000
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    text = jax.jit(
        lambda i, m, t, f: augmentation.compact_warp(i, m, t, f,
                                                     impl="pallas")
    ).lower(_s(one_chip, (n, h, h, c)), _s(one_chip, (n, 2, 2)),
            _s(one_chip, (n, 2)), _s(one_chip, (n,), jnp.bool_)
            ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == \
        len(augmentation.warp_ladder(n)) == 3


@pytest.mark.parametrize("n", [EMNIST_PARAMS, CINIC_PARAMS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_agg_compiles(one_chip, n, dtype):
    m = 13
    _assert_mosaic(lambda d, w: fa.fedavg_agg(d, w, interpret=False),
                   _s(one_chip, (m, n), dtype), _s(one_chip, (m,)))


def test_kld_score_compiles(one_chip):
    _assert_mosaic(lambda m, c: kl.kld_score(m, c, interpret=False),
                   _s(one_chip, (C,)), _s(one_chip, (K, C)))


def test_kld_score_matrix_compiles(one_chip):
    _assert_mosaic(lambda m, c: kl.kld_score_matrix(m, c, interpret=False),
                   _s(one_chip, (K // GAMMA, C)), _s(one_chip, (K, C)))


def test_kld_greedy_picks_compiles(one_chip):
    _assert_mosaic(lambda c: kl.kld_greedy_picks(c, GAMMA, interpret=False),
                   _s(one_chip, (K, C)))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_alg3_fig7_compiles(one_chip, monkeypatch, use_kernel):
    """The schedule the engine builds one round ahead: Alg. 3 (the scan,
    or the one-launch kernel) and every mediator's Fig. 7 KLD in one
    program, at the benchmark's cohort of 50 and mediators of 5."""
    import numpy as np

    from repro.core import scheduling
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    k = 50
    compiled = scheduling._alg3_fig7.lower(
        _s(one_chip, (k, C)), gamma=GAMMA, use_kernel=use_kernel).compile()
    picks, klds = compiled.out_info
    assert picks.shape == (k,) and np.dtype(picks.dtype) == np.int32
    assert [d.shape for d in klds] == [()] * (k // GAMMA)
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == use_kernel


def test_tp_rows_round_compiles(topo, monkeypatch):
    """The Astraea round with online Alg. 2 on the 2x2 (mediator, model)
    mesh with TP rows: the model axis is compiler-auto there, and XLA
    cannot partition a Mosaic kernel, so the warp must sit in a shard_map
    manual over ``model``. The engine is built on the one CPU device, then
    pointed at the described mesh and lowered from shapes alone."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core import LocalSpec, augmentation
    from repro.core.engine import EngineConfig, FLRoundEngine
    from repro.data.federated import EMNIST_LIKE, partition
    from repro.kernels import ops
    from repro.launch.mesh import make_fl_mesh
    from repro.launch.sharding import model_only_rules, param_shardings
    from repro.models.cnn import emnist_cnn
    from repro.optim import adam

    spec = dataclasses.replace(EMNIST_LIKE, num_classes=C, image_size=28)
    fed = partition(spec, num_clients=8, total_samples=400, test_samples=C,
                    sizes="instagram", global_dist="letterfreq",
                    local="random", seed=0)
    phase = augmentation.resolve_aug_mode(fed, 0.67, "online", 0)
    plan, _ = augmentation.resolve_engine_plan(phase, False, 0.67)
    cfg = EngineConfig.astraea(clients_per_round=8, gamma=2,
                               local=LocalSpec(20, 1), seed=0,
                               pad_mediators_to=4, warp_impl="pallas")
    eng = FLRoundEngine(emnist_cnn(C, 28), adam(1e-3), phase.data, cfg,
                        mesh=make_fl_mesh(mediator=1, model=1),
                        aug_plan=plan)
    data, plan_args, unperm, slot, row_to_group, m_real = \
        eng.ensure_schedule()
    args = (eng.server_state, data, plan_args, unperm, slot,
            eng._round_keys(row_to_group, m_real)) + eng.extra_args()

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("mediator", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def on_mesh(a):
        spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
            else P()
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    eng.mesh, eng._msize, eng._model_size, eng._tp_rows = mesh, 2, 2, True
    eng._param_shardings = param_shardings(eng.model.param_specs(), mesh,
                                           model_only_rules())
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    _assert_mosaic(eng._build_round_fn(None), *jax.tree.map(on_mesh, args))
