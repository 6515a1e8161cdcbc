"""Schedule packing on the host path: mean wall time of the engine's
``pack`` span (``store.place`` + ``store.plan``, with the
``store_stream`` child), read from the rounds run with the engine's
telemetry on. Moves ``round_s``."""


def read(run):
    durs = [e["dur_us"] for e in run.spans
            if e["kind"] == "span" and e["name"] == "pack"]
    return sum(durs) / len(durs) / 1e3 if durs else None
