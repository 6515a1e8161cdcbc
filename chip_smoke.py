"""Bring-up smoke run of the Astraea round engine on a TPU.

Drives the main path once through the entry points a user calls --
``FedAvgTrainer`` and ``AstraeaTrainer`` over ``FLRoundEngine`` -- at the
paper's EMNIST shape: 500 clients, 47 classes, 28x28x1 images,
letter-frequency global skew, log-normal ("instagram") client sizes, about
100,000 training samples, and the 68,873-parameter ``emnist_cnn(47, 28)``
with seeded random weights. Then checks each Pallas kernel of the FL path
against its plain-jnp reference in ``repro/kernels/ref.py``.

    python chip_smoke.py            # one chip: the rounds + kernel checks
    python chip_smoke.py --chips 4  # four chips: only the mesh comparisons

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero before printing it. With no TPU the script refuses to
run: it never falls back to the CPU, and no kernel on its path runs in
interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Size:
    """Federation, cohort and kernel-check sizes of one smoke run."""
    clients: int = 500
    classes: int = 47
    image: int = 28
    samples: int = 100_000
    test_per_class: int = 100
    cohort: int = 50            # c: clients per round
    gamma: int = 5              # clients per mediator (Astraea)
    batch: int = 20             # B
    epochs: int = 1             # E
    rounds: int = 3
    warp_batch: int = 256       # images in the warp-kernel check
    seed: int = 0


FULL = Size()
# the four-chip comparisons build four engines one after another; a quarter
# of the federation keeps them inside the time limit, at the same widths
FOUR_CHIP = dataclasses.replace(FULL, clients=128, samples=25_600, cohort=16,
                                gamma=4, rounds=2)

# max-abs tolerances of each kernel against its f32 reference
TOL = {
    # both resample at the same f32 source coordinates; what remains is the
    # rounding of a four-term bilinear sum over N(0, 1) pixels (a few 1e-7)
    "affine_warp": 1e-5,
    # f32 multiply-accumulate of normalized weights over N(0, 1) deltas
    "fedavg_agg": 1e-5,
    # scores lie in [0, log 47]; the kernel's and XLA's f32 log may differ
    # by an ulp or two
    "kld_score": 1e-5,
    "kld_score_matrix": 1e-5,
    # per-step regret: the reference score of each pick minus the least
    # reference score among the clients still unassigned at that step
    "kld_greedy_picks": 1e-5,
}
# tensor-parallel row compute splits each row's matmuls over the model
# axis, so its sums run in another order than the gathered replica's: the
# two SGD trajectories may part by this fraction of the distance travelled
TP_ROWS_TOL = 1e-3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(count: int):
    """The JAX devices, if they are ``count`` TPUs; exits otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: JAX sees {len(devs)} "
            f"{devs[0].platform} device(s). This script measures the chip "
            "and does not fall back to the CPU.")
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: expected {count} TPU chip(s), "
                         f"JAX sees {len(devs)}")
    return devs


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def build_federation(size: Size):
    """The LTRF-style federation at ``size`` and its padded-pack bytes.

    ``FederatedDataset.padded`` pads every client to the largest one, so
    the replicated store costs ``clients x pad x (image + label + mask)``
    bytes on every device -- reckoned here, before anything is packed.
    """
    from repro.data.federated import EMNIST_LIKE, partition
    spec = dataclasses.replace(EMNIST_LIKE, num_classes=size.classes,
                               image_size=size.image)
    fed = partition(spec, num_clients=size.clients,
                    total_samples=size.samples,
                    test_samples=size.test_per_class * size.classes,
                    sizes="instagram", global_dist="letterfreq",
                    local="random", seed=size.seed, name="ltrf-emnist")
    sizes = [x.shape[0] for x in fed.client_images]
    pad = max(sizes)
    sample_bytes = size.image * size.image * 1 * 4 + 4 + 4
    pack = {"clients": size.clients, "samples": int(sum(sizes)),
            "mean_client": float(sum(sizes) / len(sizes)), "pad": pad,
            "pack_bytes": size.clients * pad * sample_bytes}
    return fed, pack


def make_trainer(kind: str, fed, size: Size, **kw):
    """``FedAvgTrainer`` or ``AstraeaTrainer`` (online Alg. 2, mediators)
    over ``emnist_cnn`` at the federation's width."""
    from repro.core import LocalSpec
    from repro.core.astraea import AstraeaTrainer
    from repro.core.fedavg import FedAvgTrainer
    from repro.models.cnn import emnist_cnn
    from repro.optim import adam
    model = emnist_cnn(size.classes, size.image)
    local = LocalSpec(size.batch, size.epochs)
    if kind == "fedavg":
        return FedAvgTrainer(model, adam(1e-3), fed,
                             clients_per_round=size.cohort, local=local,
                             seed=size.seed, **kw)
    return AstraeaTrainer(model, adam(1e-3), fed,
                          clients_per_round=size.cohort, gamma=size.gamma,
                          local=local, alpha=0.67, aug_mode="online",
                          seed=size.seed, **kw)


def round_text(engine) -> str:
    """The compiled round program of ``engine``, as text, for the
    schedule it holds. Lowering re-traces the round, so read
    ``num_round_traces`` before calling this."""
    data, plan, unperm, slot, row_to_group, m_real = engine.ensure_schedule()
    keys = engine._round_keys(row_to_group, m_real)
    lowered = engine._round_program.lower(engine.server_state, data, plan,
                                          unperm, slot, keys,
                                          *engine.extra_args())
    return lowered.compile().as_text()


def run_trainer(kind: str, fed, size: Size) -> dict:
    """A few rounds of one trainer; returns its metrics and checks."""
    import jax
    import numpy as np
    from repro.core.fl import evaluate
    from repro.models.cnn import count_params
    t0 = time.perf_counter()
    tr = make_trainer(kind, fed, size)
    eng = tr.engine
    tr.run_round()
    jax.block_until_ready(tr.params)
    first_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    for _ in range(size.rounds - 1):
        tr.run_round()
    jax.block_until_ready(tr.params)
    rest_s = time.perf_counter() - t1
    m = evaluate(eng.model, tr.params, fed.test_images, fed.test_labels)
    params_finite = all(bool(np.isfinite(np.asarray(p)).all())
                        for p in jax.tree.leaves(tr.params))
    out = {"kind": kind, "rounds": size.rounds,
           "params": count_params(tr.params),
           "accuracy": m["accuracy"], "loss": m["loss"],
           "finite": params_finite and math.isfinite(m["accuracy"])
           and math.isfinite(m["loss"]),
           "num_round_traces": eng.num_round_traces,
           "setup_and_first_round_s": first_s,
           "later_rounds_s": rest_s}
    out["tpu_custom_call"] = "tpu_custom_call" in round_text(eng)
    del tr, eng
    gc.collect()
    return out


def check_kernels(size: Size, *, interpret: bool) -> list[dict]:
    """Each FL-path kernel at its main-path shape against ``kernels/ref``.

    References run with f32 matmuls at the highest precision, so they are
    the f32 answer on every backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import scheduling
    from repro.core.augmentation import warp_params
    from repro.kernels import affine_warp as aw
    from repro.kernels import fedavg_agg as fa
    from repro.kernels import kld_score as kl
    from repro.kernels import ref
    from repro.data.federated import letter_frequency_probs

    key = jax.random.PRNGKey(size.seed)
    rng = np.random.default_rng(size.seed)
    rows = []

    def record(name, shape, got, want):
        diff = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                     - jnp.asarray(want, jnp.float32))))
        rows.append({"kernel": name, "shape": shape, "max_abs_diff": diff,
                     "tol": TOL[name], "ok": diff <= TOL[name]})

    with jax.default_matmul_precision("highest"):
        b, h = size.warp_batch, size.image
        imgs = jax.random.normal(key, (b, h, h, 1), jnp.float32)
        mats, trans = warp_params(jax.random.fold_in(key, 1), b)
        coords = ref.source_coords(h, h, mats, trans)
        record("affine_warp", f"{b}x{h}x{h}x1",
               aw.resample(imgs, coords, interpret=interpret),
               ref.resample(imgs, coords))

        from repro.models.cnn import count_params, emnist_cnn
        n = count_params(emnist_cnn(size.classes, size.image).init(key))
        m = -(-size.cohort // size.gamma)
        deltas = jax.random.normal(jax.random.fold_in(key, 2), (m, n))
        w = jnp.asarray(rng.integers(1, 1000, m), jnp.float32)
        record("fedavg_agg", f"{m}x{n}",
               fa.fedavg_agg(deltas, w, interpret=interpret),
               ref.fedavg_agg(deltas, w))

        # integer histograms drawn like the federation's: LTRF global law
        k, c = size.clients, size.classes
        counts = rng.multinomial(200, letter_frequency_probs(c), size=k)
        counts = jnp.asarray(counts, jnp.float32)
        med = counts[:size.gamma].sum(0)
        record("kld_score", f"{k}x{c}",
               kl.kld_score(med, counts, interpret=interpret),
               ref.kld_score(med, counts))
        nm = k // size.gamma
        meds = counts[:nm * size.gamma].reshape(nm, size.gamma, c).sum(1)
        record("kld_score_matrix", f"{meds.shape[0]}x{k}x{c}",
               kl.kld_score_matrix(meds, counts, interpret=interpret),
               ref.kld_score_matrix(meds, counts))

        picks = np.asarray(kl.kld_greedy_picks(counts, size.gamma,
                                               interpret=interpret))
        if sorted(picks.tolist()) != list(range(k)):
            fail("kld_greedy_picks is not a permutation of the clients")
        # replay: the open mediator's histogram before every step, then
        # every step's reference scores in one (K, K) sweep
        cnp = np.asarray(counts)
        before = np.zeros_like(cnp)
        for s in range(1, k):
            if s % size.gamma:
                before[s] = before[s - 1] + cnp[picks[s - 1]]
        scores = np.asarray(ref.kld_score_matrix(jnp.asarray(before), counts))
        taken = np.zeros((k, k), bool)
        for s in range(1, k):
            taken[s] = taken[s - 1]
            taken[s, picks[s - 1]] = True
        best = np.where(taken, np.inf, scores).min(axis=1)
        regret = scores[np.arange(k), picks] - best
        scan = np.asarray(scheduling._greedy_picks(counts, size.gamma))
        rows.append({"kernel": "kld_greedy_picks",
                     "shape": f"{k}x{c} gamma={size.gamma}",
                     "max_abs_diff": float(regret.max()),
                     "tol": TOL["kld_greedy_picks"],
                     "ok": float(regret.max()) <= TOL["kld_greedy_picks"],
                     "same_picks_as_xla_scan": int((scan == picks).sum())})
    return rows


def _astraea_engine(fed, size: Size, mesh, opt=None, **cfg_kw):
    """The engine ``AstraeaTrainer`` builds (online Alg. 2, mediators),
    built directly so the comparisons can set ``row_exec`` and
    ``tp_rows``."""
    from repro.core import LocalSpec, augmentation
    from repro.core.engine import EngineConfig, FLRoundEngine
    from repro.models.cnn import emnist_cnn
    from repro.optim import adam
    phase = augmentation.resolve_aug_mode(fed, 0.67, "online", size.seed)
    plan, _ = augmentation.resolve_engine_plan(phase, False, 0.67)
    cfg = EngineConfig.astraea(
        clients_per_round=size.cohort, gamma=size.gamma,
        local=LocalSpec(size.batch, size.epochs), seed=size.seed,
        pad_mediators_to=-(-size.cohort // size.gamma), **cfg_kw)
    return FLRoundEngine(emnist_cnn(size.classes, size.image),
                         opt or adam(1e-3), phase.data, cfg, mesh=mesh,
                         aug_plan=plan)


def _rounds(engine, size: Size):
    import jax
    import numpy as np
    for _ in range(size.rounds):
        engine.run_round()
    return jax.tree.map(np.asarray, engine.params)


def _max_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _devices_of(arr) -> set:
    return {s.device for s in arr.addressable_shards}


def sharded_vs_single(fed, size: Size) -> dict:
    """``store="sharded"`` on a 4x1 mediator mesh against the same run on
    a 1-device mesh, ``row_exec="map"``: the params must be bitwise equal
    (the 4-device contract of tests/test_client_store.py), with the client
    shards and the replicated params on all four devices."""
    import jax
    from repro.launch.mesh import make_mediator_mesh
    e4 = _astraea_engine(fed, size, make_mediator_mesh(4), store="sharded",
                         row_exec="map")
    devs = set(jax.devices())
    params_on = set.intersection(*[_devices_of(p)
                                   for p in jax.tree.leaves(e4.params)])
    placed = {
        "data_devices": len(_devices_of(e4.store._x)),
        "data_rows_per_device": sorted(
            {s.data.shape[0] for s in e4.store._x.addressable_shards}),
        "param_devices": len(params_on),
        "per_device_bytes": e4.store.per_device_bytes(),
    }
    if _devices_of(e4.store._x) != devs or params_on != devs:
        fail(f"client data or params are not on all four devices: {placed}")
    p4 = _rounds(e4, size)
    traces = e4.num_round_traces
    del e4
    gc.collect()
    e1 = _astraea_engine(fed, size, make_mediator_mesh(1), store="sharded",
                         row_exec="map")
    p1 = _rounds(e1, size)
    del e1
    gc.collect()
    diff = _max_diff(p4, p1)
    return {"max_abs_diff": diff, "bitwise": diff == 0.0,
            "num_round_traces": traces, "placement": placed}


def tp_rows_vs_gather(fed, size: Size) -> dict:
    """The 2x2 (mediator, model) mesh with ``tp_rows`` resolved on ("auto"
    on a TPU) against ``tp_rows=False``: the params' distance, relative to
    how far the gather run moved them, within ``TP_ROWS_TOL``. Plain SGD,
    not Adam: Adam's first steps move every weight by about the learning
    rate times the sign of its gradient, so a rounding flip of a near-zero
    gradient would show as a full step."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_fl_mesh
    from repro.optim import sgd
    mesh = make_fl_mesh(mediator=2, model=2)
    et = _astraea_engine(fed, size, mesh, opt=sgd(0.05))
    if not et._tp_rows:
        fail("tp_rows='auto' did not resolve on for the 2x2 mesh")
    p0 = jax.tree.map(np.asarray, et.params)
    pt = _rounds(et, size)
    traces = et.num_round_traces
    del et
    gc.collect()
    eg = _astraea_engine(fed, size, mesh, opt=sgd(0.05), tp_rows=False)
    pg = _rounds(eg, size)
    del eg
    gc.collect()
    diff, moved = _max_diff(pt, pg), _max_diff(pg, p0)
    rel = diff / max(moved, 1e-30)
    return {"max_abs_diff": diff, "max_abs_update": moved, "relative": rel,
            "tol": TP_ROWS_TOL, "ok": rel <= TP_ROWS_TOL,
            "num_round_traces": traces}


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the rounds and kernel checks on one chip; "
                         "4: only the four-chip mesh comparisons")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")

    if args.chips == 4:
        fed, pack = build_federation(FOUR_CHIP)
        log(f"federation: {json.dumps(pack)}")
        res = sharded_vs_single(fed, FOUR_CHIP)
        log(f"sharded 4x1 vs 1 device: {json.dumps(res)}")
        if not res["bitwise"] or res["num_round_traces"] != 1:
            fail("sharded 4x1 run differs from the 1-device run, or retraced")
        res = tp_rows_vs_gather(fed, FOUR_CHIP)
        log(f"tp_rows 2x2 vs gather: {json.dumps(res)}")
        if not res["ok"] or res["num_round_traces"] != 1:
            fail("tp_rows params are outside tolerance, or it retraced")
    else:
        t0 = time.perf_counter()
        fed, pack = build_federation(FULL)
        log(f"federation ({time.perf_counter() - t0:.1f} s): "
            f"{json.dumps(pack)}")
        log(f"padded pack per device: {pack['pack_bytes'] / 2**30:.3f} GiB")
        for kind in ("fedavg", "astraea"):
            res = run_trainer(kind, fed, FULL)
            stats = devs[0].memory_stats() or {}
            res["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
            log(f"{kind}: {json.dumps(res)}")
            if not res["finite"]:
                fail(f"{kind}: non-finite loss, accuracy or params")
            if res["num_round_traces"] != 1:
                fail(f"{kind}: num_round_traces={res['num_round_traces']}")
            if kind == "astraea" and not res["tpu_custom_call"]:
                fail("astraea: no tpu_custom_call in the compiled round")
        for row in check_kernels(FULL, interpret=False):
            log(f"kernel: {json.dumps(row)}")
            if not row["ok"]:
                fail(f"{row['kernel']} max_abs_diff {row['max_abs_diff']} "
                     f"> {row['tol']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
