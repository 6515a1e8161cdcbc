"""Alg. 3 on the host path: mean wall time of the engine's ``reschedule``
span (``core/engine.py:_pack_schedule`` -> ``core/scheduling.py``), read
from the rounds run with the engine's telemetry on. Moves ``round_s``."""


def read(run):
    durs = [e["dur_us"] for e in run.spans
            if e["kind"] == "span" and e["name"] == "reschedule"]
    return sum(durs) / len(durs) / 1e3 if durs else None
