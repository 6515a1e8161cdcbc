"""The program's own spans on the device clock.

Every span of the round engine opens a ``jax.profiler.TraceAnnotation``
named ``astraea.<span>`` (``src/repro/obs/trace.py``), with telemetry on
or off, so a profiler trace holds each round's host phases beside the
device's operations. ``reduce_spans`` intersects them with the device's
idle time:

* the window and the device's busy time are ``bench/xplane.py``'s: from
  the first to the last of the benchmark's ``bench.*`` annotations and
  the device operations, busy being the union of the ``XLA Ops``
  intervals; idle is the rest of the window;
* per span name: ``count``, ``seconds`` (summed durations) and
  ``idle_s``, the device-idle time inside the union of the span's
  intervals -- by intersection, not by a gap's midpoint -- averaged over
  the chips like ``busy_s``;
* ``read_idle_s``: idle inside the ``host_read.*`` spans, where host and
  chip both wait on a device->host read; ``bubble_idle_s``: idle inside
  ``round`` spans and outside every read, the idle that the host's own
  work causes; ``round_idle_s``: idle inside ``round`` spans;
* ``rounds``: each ``round`` span's children, milliseconds per name, so
  a stalled round shows.

``per_round`` turns the reduction into the numbers a round reads:
``alg3_wait_ms``, ``read_idle_ms``, ``host_bubble_ms``, ``host_reads``.

Run on the chip, it measures one cell's set-up and three windows of the
same rounds on the same trainer: untraced, profiled with telemetry off,
and with a recording ``Telemetry`` (the cost of tracing), then prints the
per-span idle table and one JSON line:

    python bench/spans.py --workload emnist-ltrf.astraea --seed 7
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.xplane import BENCH_PREFIX, _clip, _union  # noqa: E402

SPAN_PREFIX = "astraea."
READ_PREFIX = "host_read."
ROUNDS = 5


def _complement(merged, lo, hi):
    """The stretches of ``[lo, hi]`` outside the sorted disjoint
    ``merged`` intervals."""
    edges = [lo] + [x for se in merged for x in se] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def _intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals) / 1e9


def reduce_spans(device: dict, host: list) -> dict:
    """The reduction over plain events, as ``bench/xplane.read_xplane``
    gives them: ``device`` maps a chip to its ``(name, start_ns,
    end_ns)`` operations, ``host`` lists ``(name, start_ns, end_ns)``
    host events of every thread. Without a device plane the device
    counts as one chip that ran nothing."""
    marks = [(s, e) for n, s, e in host if n.startswith(BENCH_PREFIX)]
    ends = marks + [(s, e) for ops in device.values() for _, s, e in ops]
    spans: dict[str, list] = {}
    for n, s, e in sorted(host, key=lambda ev: ev[1]):
        if n.startswith(SPAN_PREFIX):
            spans.setdefault(n[len(SPAN_PREFIX):], []).append((s, e))
    if not ends:
        return {"window_s": 0.0, "idle_s": 0.0, "spans": {},
                "read_idle_s": 0.0, "round_idle_s": 0.0,
                "bubble_idle_s": 0.0, "rounds": []}
    lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    chips = [_complement(_union(_clip([(s, e) for _, s, e in ops], lo, hi)),
                         lo, hi) for ops in device.values()] or [[(lo, hi)]]

    def idle_in(merged):
        return sum(_length(_intersect(idle, merged))
                   for idle in chips) / len(chips)

    def merged(intervals):
        return _union(_clip(intervals, lo, hi))

    reads = merged([iv for n, ivs in spans.items()
                    if n.startswith(READ_PREFIX) for iv in ivs])
    rounds = merged(spans.get("round", []))
    round_idle = idle_in(rounds)
    table = {n: {"count": len(ivs),
                 "seconds": sum(e - s for s, e in ivs) / 1e9,
                 "idle_s": idle_in(merged(ivs))}
             for n, ivs in sorted(spans.items())}
    per_round = []
    for rs, re_ in spans.get("round", []):
        kids: dict[str, float] = {}
        for n, ivs in spans.items():
            for s, e in ivs:
                if rs <= s and e <= re_:
                    kids[n] = kids.get(n, 0.0) + (e - s) / 1e6
        per_round.append(kids)
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": sum(_length(idle) for idle in chips) / len(chips),
            "spans": table,
            "read_idle_s": idle_in(reads),
            "round_idle_s": round_idle,
            "bubble_idle_s": round_idle - idle_in(_intersect(reads, rounds)),
            "rounds": per_round}


def per_round(r: dict) -> dict | None:
    """The reduction's numbers per ``round`` span: ``alg3_wait_ms``
    (mean ``host_read.alg3_picks``; ``None`` without one),
    ``read_idle_ms``, ``host_bubble_ms``, ``round_idle_ms`` and
    ``host_reads`` (``host_read.*`` events). ``None`` where the trace
    holds no ``round`` span (a program without the annotations)."""
    n = r["spans"].get("round", {}).get("count", 0)
    if not n:
        return None
    picks = r["spans"].get(READ_PREFIX + "alg3_picks")
    return {"alg3_wait_ms": 1e3 * picks["seconds"] / picks["count"]
            if picks else None,
            "read_idle_ms": 1e3 * r["read_idle_s"] / n,
            "host_bubble_ms": 1e3 * r["bubble_idle_s"] / n,
            "round_idle_ms": 1e3 * r["round_idle_s"] / n,
            "host_reads": sum(v["count"] for k, v in r["spans"].items()
                              if k.startswith(READ_PREFIX)) / n}


def timed_rounds(tr, rounds: int) -> float:
    """Host seconds per round of ``rounds`` rounds run back to back,
    ending in ``block_until_ready``."""
    import jax
    t = time.perf_counter()
    for _ in range(rounds):
        tr.run_round()
    jax.block_until_ready(tr.params)
    return (time.perf_counter() - t) / rounds


def profiled_rounds(tr, rounds: int, log_dir: str) -> float:
    """``bench/run.py``'s profiler window: ``rounds`` rounds, each in a
    ``bench.run_round`` annotation, the python tracer off, the trace
    written under ``log_dir``; returns host seconds per round."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t = time.perf_counter()
    for _ in range(rounds):
        with jax.profiler.TraceAnnotation("bench.run_round"):
            tr.run_round()
    jax.block_until_ready(tr.params)
    wall = time.perf_counter() - t
    jax.profiler.stop_trace()
    return wall / rounds


def measure(tr, rounds: int) -> dict:
    """The three windows on one trainer: untraced, profiled, recording
    telemetry; the profiled one reduced by ``reduce_spans``."""
    from bench import xplane
    from repro.obs.telemetry import Telemetry, as_telemetry
    out = {"untraced_round_s": timed_rounds(tr, rounds)}
    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        out["profiled_round_s"] = profiled_rounds(tr, rounds, tmp)
        paths = list(Path(tmp).rglob("*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {tmp}, "
                               f"found {len(paths)}")
        device, host, _ = xplane.read_xplane(str(paths[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    eng = tr.engine
    eng.telemetry = eng.store.telemetry = Telemetry()
    try:
        out["telemetry_round_s"] = timed_rounds(tr, rounds)
    finally:
        eng.telemetry = eng.store.telemetry = as_telemetry(None)
    out["spans"] = reduce_spans(device, host)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    args = ap.parse_args(argv)
    from bench import registry, run
    cell = registry.cell(args.workload)
    devices = run.require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with jax.default_matmul_precision(cell.config["precision"]["matmul"]):
        s = run.checked_setup(cell, args.seed, devices)
        out = measure(s.trainer, args.rounds)
    r = out["spans"]
    print(f"{'span':28s} {'count':>5s} {'seconds':>9s} {'idle_s':>9s}")
    for name, row in r["spans"].items():
        print(f"{name:28s} {row['count']:5d} {row['seconds']:9.4f} "
              f"{row['idle_s']:9.4f}")
    for i, kids in enumerate(r["rounds"]):
        print(f"round {i}: " + json.dumps(
            {k: round(v, 3) for k, v in kids.items()}))
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": devices[0].device_kind, **out,
                      "per_round": per_round(r)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
