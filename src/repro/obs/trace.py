"""Host-side span tracing for the federated round path.

A ``Tracer`` records a tree of **spans** -- named wall-clock intervals
with attributes -- plus zero-duration **instant** events, all on the
host, entirely outside jit. The engine opens spans around its host-side
phases (``round`` > ``reschedule``, ``host_read.alg3_picks`` /
``schedule_stats``, ``pack`` > ``store_stream``, ``round_keys``,
``round_program``; async ``wave``/``commit``) so a round's wall-clock has
one navigable timeline instead of being smeared across ad-hoc prints and
bench JSONs.

Every span, recorded or not, is also a ``jax.profiler.TraceAnnotation``
named ``astraea.<span>``: under a profiler session the host phases land on
the device trace's clock, and without one the annotation is a TraceMe
that does nothing (``obs/README.md``).

Two export formats, both derived from the same event list:

* **JSONL** (``events.jsonl``): one JSON object per line, schema-versioned
  (``SCHEMA_VERSION``). Machine-diffable; ``validate_events`` checks the
  schema and the nesting invariants (parents exist, child intervals sit
  inside their parent's interval).
* **Chrome trace** (``trace.json``): the Trace Event Format consumed by
  Perfetto / ``chrome://tracing`` -- complete ``"X"`` events with ``ts``/
  ``dur`` in microseconds.

Device-sync discipline: a span only calls ``jax.block_until_ready`` on
values explicitly registered via ``Span.sync_on`` and only at span close
-- so timings are honest (the async dispatch queue is drained before the
clock is read) but NOTHING is blocked on when tracing is off: the no-op
telemetry path (``obs.telemetry.NULL_TELEMETRY``) never touches a device
value, which is what keeps telemetry-off rounds free of extra syncs.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation

#: bump when the JSONL event schema changes shape (validators pin this)
SCHEMA_VERSION = 1

#: prefix of every span's profiler annotation (``astraea.round``, ...)
ANNOTATION_PREFIX = "astraea."

#: keys every JSONL event must carry
EVENT_KEYS = ("schema", "kind", "id", "parent", "name", "ts_us", "dur_us",
              "attrs")


class Span:
    """One open interval; use as a context manager via ``Tracer.span``."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "t0", "t1",
                 "_tracer", "_sync", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, span_id: int,
                 parent_id: int | None, attrs: dict):
        self.name, self.attrs = name, dict(attrs)
        self.span_id, self.parent_id = span_id, parent_id
        self._tracer = tracer
        self._sync: list[Any] = []
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + name)
        self.t0 = self.t1 = 0.0

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (KLD mean, bytes, ...)."""
        self.attrs.update(attrs)
        return self

    def sync_on(self, value: Any) -> "Span":
        """Register a (pytree of) device value(s) to ``block_until_ready``
        at span close, so the span's duration includes the device work it
        dispatched. Only ever called with tracing enabled."""
        self._sync.append(value)
        return self

    @property
    def duration_s(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def __enter__(self) -> "Span":
        tracer = self._tracer
        self._annotation.__enter__()
        tracer._stack.append(self.span_id)
        self.t0 = tracer.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._sync:
            import jax
            jax.block_until_ready(self._sync)
        tracer = self._tracer
        self.t1 = tracer.clock()
        assert tracer._stack and tracer._stack[-1] == self.span_id, \
            "span close out of order (spans must nest)"
        tracer._stack.pop()
        self._annotation.__exit__(exc_type, exc, tb)
        tracer._emit(self)


class Tracer:
    """Append-only span/instant recorder with JSONL + Chrome-trace export.

    ``clock`` defaults to ``time.perf_counter`` (monotonic); tests inject
    a fake clock for deterministic timestamps.
    """

    def __init__(self, *, clock: Callable[[], float] | None = None):
        self.clock = clock or time.perf_counter
        self.events: list[dict] = []
        self._stack: list[int] = []
        self._next_id = 0
        self._epoch = self.clock()

    # ---- recording ----
    def span(self, name: str, **attrs) -> Span:
        sid, self._next_id = self._next_id, self._next_id + 1
        parent = self._stack[-1] if self._stack else None
        return Span(self, name, sid, parent, attrs)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker (e.g. a ledger charge) at the current
        nesting level."""
        sid, self._next_id = self._next_id, self._next_id + 1
        now = self.clock()
        self.events.append(self._event("instant", sid,
                                       self._stack[-1] if self._stack
                                       else None,
                                       name, now, now, attrs))

    def _emit(self, span: Span) -> None:
        self.events.append(self._event("span", span.span_id, span.parent_id,
                                       span.name, span.t0, span.t1,
                                       span.attrs))

    def _event(self, kind, sid, parent, name, t0, t1, attrs) -> dict:
        return {"schema": SCHEMA_VERSION, "kind": kind, "id": sid,
                "parent": parent, "name": name,
                "ts_us": (t0 - self._epoch) * 1e6,
                "dur_us": (t1 - t0) * 1e6,
                "attrs": _jsonable(attrs)}

    # ---- export ----
    def to_jsonl(self) -> str:
        return "".join(json.dumps(e, sort_keys=True) + "\n"
                       for e in self.events)

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_jsonl())

    def to_chrome_trace(self) -> dict:
        """Trace Event Format: complete ``"X"`` events, us timestamps --
        loadable in Perfetto / chrome://tracing as-is."""
        trace_events = []
        for e in self.events:
            trace_events.append({
                "name": e["name"], "cat": "astraea",
                "ph": "X" if e["kind"] == "span" else "i",
                "ts": e["ts_us"], "dur": e["dur_us"],
                "pid": 0, "tid": 0,
                "args": dict(e["attrs"], event_id=e["id"],
                             parent=e["parent"]),
            })
        return {"traceEvents": trace_events, "displayTimeUnit": "ms",
                "otherData": {"schema": SCHEMA_VERSION}}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def _jsonable(attrs: dict) -> dict:
    """Coerce numpy / jax scalars so every event round-trips json.dumps."""
    out = {}
    for k, v in attrs.items():
        if hasattr(v, "item") and getattr(v, "ndim", None) in (0, None):
            try:
                v = v.item()
            except Exception:
                v = repr(v)
        elif not isinstance(v, (str, int, float, bool, type(None), list,
                                dict, tuple)):
            v = repr(v)
        out[k] = v
    return out


def validate_events(events: list[dict]) -> None:
    """Raise ``ValueError`` unless ``events`` is a schema-valid span tree.

    Checks: every event carries exactly the schema-versioned key set;
    every ``parent`` id names an emitted span; every child span's
    interval nests inside its parent's. Used by the telemetry tests and
    the CI smoke leg against freshly emitted JSONL.
    """
    spans: dict[int, dict] = {}
    for i, e in enumerate(events):
        missing = [k for k in EVENT_KEYS if k not in e]
        if missing:
            raise ValueError(f"event {i} missing keys {missing}: {e}")
        if e["schema"] != SCHEMA_VERSION:
            raise ValueError(f"event {i} schema {e['schema']} != "
                             f"{SCHEMA_VERSION}")
        if e["kind"] not in ("span", "instant"):
            raise ValueError(f"event {i} bad kind {e['kind']!r}")
        if e["dur_us"] < 0:
            raise ValueError(f"event {i} negative duration")
        if e["kind"] == "span":
            spans[e["id"]] = e
    for e in events:
        p = e["parent"]
        if p is None:
            continue
        if p not in spans:
            raise ValueError(f"event {e['id']} parent {p} never emitted "
                             f"as a span")
        parent = spans[p]
        lo, hi = parent["ts_us"], parent["ts_us"] + parent["dur_us"]
        if not (lo - 1e-3 <= e["ts_us"] and
                e["ts_us"] + e["dur_us"] <= hi + 1e-3):
            raise ValueError(
                f"event {e['id']} ({e['name']}) interval "
                f"[{e['ts_us']}, {e['ts_us'] + e['dur_us']}] escapes "
                f"parent {p} ({parent['name']}) [{lo}, {hi}]")


def load_jsonl(path: str) -> list[dict]:
    """Parse an ``events.jsonl`` file back into the event list."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]
