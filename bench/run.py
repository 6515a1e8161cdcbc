"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload emnist-ltrf.astraea --seed 7 \
        --seconds 10 --trace 0

The cell names a configuration and a traffic mix (``BENCHMARK.json``).
Set-up generates the federation and the initial weights from ``--seed``,
builds the trainer, and drives it through the checked rounds, which also
compile and warm every program the window runs. Then:

* ``--trace 0``: the window calls ``run_round`` back to back for
  ``--seconds`` and ends in ``block_until_ready``; the end-to-end metrics
  are the round time, the peak device memory and the set-up time.
* ``--trace 1``: a profiler window of a few rounds with telemetry off,
  then a few rounds with the engine's host spans on; the per-layer
  metrics are read from both by the readers in ``bench/metrics/``.

After either, the program's state is freed and the reference follows the
checked rounds (``bench/check.py``). The last line of standard output is
one JSON object; the numbers compared, beside their limits, are the last
lines of standard error and the result's last key. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, counting, federation, peaks, program  # noqa: E402
from bench import reference, registry  # noqa: E402

PROFILE_ROUNDS = 5          # rounds in the profiler window (--trace 1)
SPAN_ROUNDS = 5             # rounds with the engine's host spans on


def info(msg: str) -> None:
    print(msg, flush=True)


def require_chip(chips: int):
    """The first ``chips`` TPU devices; exits non-zero otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: no TPU: JAX sees {len(devs)} {devs[0].platform} "
            "device(s); the benchmark measures the chip and has no CPU "
            "fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compilations reported through ``jax.monitoring``. The
    listener registry is the process's, so one counter serves a process."""

    _instance = None

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.count += 1


def schedule_of(tr) -> list[list[int]]:
    return [list(g) for g in tr.engine.last_groups]


def round_operands(tr):
    """The operands of the round just run that say which slots it trained:
    the store's per-sample mask, each row's client indices and the slot
    mask, as the engine fed them to the round program (device arrays, read
    once the profiler window has closed). ``None`` for a store that does
    not hold the mask whole."""
    eng = tr.engine
    if eng.store.policy != "replicated":
        return None
    data_args, plan_args, _unperm, slot, _rows, _m_real = eng._schedule
    return data_args[2], plan_args[0], slot


def slot_counts(operands: list) -> list[tuple[float, int]] | None:
    """``(real samples, padded slots)`` that each round's program trained:
    the masked-in samples of the clients in its rows, and every slot of
    every row (``rows x gamma x pad``)."""
    if not operands or any(o is None for o in operands):
        return None
    per_client = {}
    out = []
    for mask, idx, slot in operands:
        if id(mask) not in per_client:
            per_client[id(mask)] = np.asarray(mask).sum(axis=1)
        sizes = per_client[id(mask)]
        idx, slot = np.asarray(idx), np.asarray(slot)
        out.append((float((sizes[idx] * slot).sum()),
                    int(idx.size * mask.shape[1])))
    return out


def checked_setup(cell: registry.Cell, seed: int, devices, *,
                  fault=None) -> SimpleNamespace:
    """Set-up as every run makes it: the federation and the initial weights
    from ``seed``, the trainer, and the checked rounds driven through the
    trainer's own ``run_round`` (which also compiles and warms every
    program the window runs), keeping the weights and schedule after each.
    ``fault``, when given, is called with the trainer once it is built
    (tests plant broken paths through it). Runs under the caller's matmul
    precision."""
    import jax
    cfg, traffic = cell.config, cell.traffic
    pseed = seed % (2 ** 31 - 2)
    times = {}

    t = time.perf_counter()
    fed = federation.generate(cfg, seed)
    times["federation_s"] = time.perf_counter() - t
    pack = federation.pack_reckoning(cfg, traffic["batch_size"], fed.counts)
    info(f"pack: {json.dumps(pack)}")

    t = time.perf_counter()
    params0 = reference.make_init(cfg)(jax.random.PRNGKey(pseed))
    params0_host = jax.tree.map(lambda a: np.array(a, copy=True), params0)
    times["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    tr = program.build_trainer(cfg, traffic, fed, pseed, params0,
                               devices=devices)
    if fault is not None:
        fault(tr)
    times["trainer_s"] = time.perf_counter() - t
    info(f"store: {json.dumps(tr.engine.store.stats(), default=str)}")

    prog_params, prog_sched = [], []
    for r in range(traffic["steps_checked"]):
        t = time.perf_counter()
        tr.run_round()
        prog_params.append(program.host_params(tr))
        prog_sched.append(schedule_of(tr))
        times[f"round{r}_s"] = time.perf_counter() - t
    return SimpleNamespace(fed=fed, pseed=pseed,
                           params0_host=params0_host, trainer=tr,
                           prog_params=prog_params, prog_sched=prog_sched,
                           times=times)


def reference_rounds(cell: registry.Cell, s: SimpleNamespace, **ref_kw
                     ) -> tuple[list, list]:
    """The reference over the checked rounds of set-up ``s``, from the same
    federation and weights: ``(params, schedules)``, one entry a round.
    ``ref_kw`` goes to ``reference.run_rounds`` (the control's dtype, a
    planted fault)."""
    cfg, traffic, fed = cell.config, cell.traffic, s.fed
    return reference.run_rounds(
        cfg, traffic, fed.client_images, fed.client_labels, fed.counts,
        s.params0_host, s.pseed, traffic["steps_checked"], **ref_kw)


def check_numbers(cell: registry.Cell, s: SimpleNamespace, ref: tuple,
                  candidate: tuple | None = None) -> dict:
    """``check.compare``'s numbers of ``candidate`` -- the program's
    checked rounds of set-up ``s`` unless given -- against the reference
    rounds ``ref``."""
    params, sched = candidate or (s.prog_params, s.prog_sched)
    losses = reference.loss_on(cell.config, s.fed.test_images,
                               s.fed.test_labels)
    return check.compare(params, sched, *ref, s.params0_host, losses)


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             devices, *, t0: float, limits: dict, fault=None) -> dict:
    """One run of ``cell`` at the matmul precision its configuration
    states (the round program is traced under it); returns the result
    object. ``fault`` is handed to ``checked_setup``."""
    import jax
    with jax.default_matmul_precision(cell.config["precision"]["matmul"]):
        return _run_cell(cell, seed, seconds, trace, devices, t0=t0,
                         limits=limits, fault=fault)


def _run_cell(cell, seed, seconds, trace, devices, *, t0, limits, fault):
    import jax
    counter = CompileCounter.get()
    compiles_before = counter.count
    s = checked_setup(cell, seed, devices, fault=fault)
    tr = s.trainer
    setup_s = time.perf_counter() - t0
    s.times["compiles"] = counter.count - compiles_before
    info(f"setup: {json.dumps(s.times)}")

    compiles0 = counter.count
    run = SimpleNamespace(cfg=cell.config, traffic=cell.traffic,
                          device_kind=devices[0].device_kind,
                          counting=counting)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if trace:
        metrics, breakdown = _traced(tr, run, cell, device)
        rounds = run.rounds
    else:
        marks = []
        t_s = time.perf_counter()
        deadline = t_s + seconds
        while time.perf_counter() < deadline:
            tr.run_round()
            marks.append(time.perf_counter())
        jax.block_until_ready(tr.params)
        window = time.perf_counter() - t_s
        rounds = len(marks)
        metrics = {"round_s": {"value": window / rounds, "unit": "s"}}
        # the host's time between successive returns of run_round, which
        # shows in which round a stall came
        steps = np.diff([t_s] + marks)
        info("window: " + json.dumps(
            {"rounds": rounds, "seconds": window,
             "host_round_s": [round(x, 4) for x in steps.tolist()]}))
    info(f"compiles in window: {counter.count - compiles0}")
    finite = all(bool(np.isfinite(a).all())
                 for a in jax.tree.leaves(program.host_params(tr)))
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    device["memory_peak_bytes"] = peak
    if not trace:
        metrics["hbm_peak_gb"] = {
            "value": None if peak is None else peak / 1e9, "unit": "GB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
    del tr
    s.trainer = None
    gc.collect()

    t = time.perf_counter()
    numbers = check_numbers(cell, s, reference_rounds(cell, s))
    info(f"reference: {time.perf_counter() - t:.3f} s")
    correct, table = check.verdict(numbers, limits)
    correct = correct and finite
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": 0 if finite else rounds,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = table
    return result


def _traced(tr, run, cell, device):
    """The profiler window, then the span rounds; returns the per-layer
    metrics and the breakdown."""
    import jax
    from bench import xplane
    from repro.obs.telemetry import Telemetry, as_telemetry

    run.peaks = peaks.peaks_for(run.device_kind)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        operands = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # keep the host's pace as is
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t_s = time.perf_counter()
        for _ in range(PROFILE_ROUNDS):
            with jax.profiler.TraceAnnotation("bench.run_round"):
                tr.run_round()
            operands.append(round_operands(tr))
        jax.block_until_ready(tr.params)
        wall = time.perf_counter() - t_s
        jax.profiler.stop_trace()
        run.trace = xplane.reduce_dir(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    run.rounds, run.profile_wall_s = PROFILE_ROUNDS, wall

    tel = Telemetry()
    eng = tr.engine
    eng.telemetry = eng.store.telemetry = tel
    for _ in range(SPAN_ROUNDS):
        tr.run_round()
        operands.append(round_operands(tr))
    eng.telemetry = eng.store.telemetry = as_telemetry(None)
    run.spans = tel.tracer.events
    run.slots = slot_counts(operands)
    run.profile_slots = run.slots and run.slots[:PROFILE_ROUNDS]

    metrics = {}
    for m in cell.per_layer:
        value = registry.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ops = list(run.trace["op_seconds"].items())
    breakdown = {"device_ops": [list(kv) for kv in ops[:10]],
                 "idle_gaps": run.trace["gaps"][:10]}
    info("trace: " + json.dumps(
        {"window_s": run.trace["window_s"], "busy_s": run.trace["busy_s"],
         "op_stats": {n: run.trace["op_stats"].get(n, "")[:400]
                      for n, _ in ops[:8]}}))
    return metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    limits = registry.limits(cell.name)
    devices = require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    # every program of the run in the cache, the small ones too, so that
    # only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t0=T_PROCESS, limits=limits)
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
