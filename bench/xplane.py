"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

* Device planes are those named ``/device:TPU:<n>``; their operations are
  the events of the ``XLA Ops`` line.
* The window runs from the first to the last event of the benchmark's own
  host annotations (``bench.*``) and the device operations together.
* ``busy_s``: the union of the device's operation intervals inside the
  window (averaged over the chips), so overlapping events count once.
* ``op_seconds``: device seconds per operation name, largest first.
* ``gaps``: every stretch of the window in which no operation ran on the
  device, attributed to the innermost host event (on any host thread,
  other than the benchmark's own annotation) that covers the middle of
  the stretch -- what the host was doing while the chip waited -- summed
  per name, largest first. A stretch no host event covers is ``idle``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench."


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _cover(events: list, points: list) -> list[str]:
    """For each of the ascending ``points``, the name of the shortest of
    the start-sorted ``(start, end, name)`` events that covers it."""
    out, active, i = [], [], 0
    for p in points:
        while i < len(events) and events[i][0] <= p:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] >= p]
        out.append(min(active, key=lambda ev: ev[1] - ev[0])[2]
                   if active else "idle")
    return out


def reduce_events(device: dict, host: list, op_stats: dict | None = None
                  ) -> dict:
    """The reduction over plain events. ``device`` maps a chip to its
    ``(name, start_ns, end_ns)`` operations; ``host`` lists
    ``(name, start_ns, end_ns)`` host events of every thread;
    ``op_stats`` maps an operation name to the text of its first event's
    statistics (what the trace says the operation is)."""
    starts = [s for ops in device.values() for _, s, _ in ops]
    ends = [e for ops in device.values() for _, _, e in ops]
    marks = [(s, e) for n, s, e in host if n.startswith(BENCH_PREFIX)]
    starts += [s for s, _ in marks]
    ends += [e for _, e in marks]
    if not starts:
        return {"window_s": 0.0, "busy_s": 0.0, "op_seconds": {},
                "gaps": [], "op_stats": op_stats or {}}
    lo, hi = min(starts), max(ends)
    per_op: dict[str, float] = defaultdict(float)
    busy, gaps = 0.0, defaultdict(float)
    others = sorted((s, e, n) for n, s, e in host
                    if not n.startswith(BENCH_PREFIX))
    for ops in device.values():
        for name, s, e in ops:
            per_op[name] += (e - s) / 1e9
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for se in merged for x in se] + [hi]
        stretches = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                     if ge > gs]
        for (gs, ge), label in zip(stretches, _cover(
                others, [(gs + ge) / 2 for gs, ge in stretches])):
            gaps[label] += (ge - gs) / 1e9
    n = max(len(device), 1)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n,
            "op_seconds": {k: v / n for k, v in
                           sorted(per_op.items(), key=lambda kv: -kv[1])},
            "gaps": [[k, v / n] for k, v in
                     sorted(gaps.items(), key=lambda kv: -kv[1])],
            "op_stats": op_stats or {}}


def read_xplane(path: str) -> tuple[dict, list, dict]:
    """``(device ops per chip, host events, op statistics)`` of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host, stats = {}, [], {}
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) \
                and name[len(DEVICE_PREFIX):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
                    if e.name not in stats:
                        stats[e.name] = " ".join(
                            f"{k}={v}" for k, v in e.stats)[:2000]
            device[name] = ops
        elif name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return device, host, stats


def reduce_file(path: str) -> dict:
    return reduce_events(*read_xplane(path))


def reduce_dir(log_dir: str) -> dict:
    """Reduce the one trace ``jax.profiler.start_trace(log_dir)`` wrote."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return reduce_file(paths[0])
