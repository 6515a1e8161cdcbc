"""The engine host path: device->host reads per round, the mean of the
``round`` spans' ``host_reads`` attribute -- the program's own count of
the reads made through ``repro.obs.host_read`` (the Alg. 3 picks and the
Fig. 7 KLD of each mediator), read from the rounds run with the engine's
telemetry on. Each read makes the host wait for the device queue. The
count is the same with telemetry off: ``bench/spans.py`` counts the
``astraea.host_read.*`` annotations of the profiler window. ``None``
where the round spans carry no count. Moves ``round_s``."""


def read(run):
    counts = [e["attrs"]["host_reads"] for e in run.spans
              if e["kind"] == "span" and e["name"] == "round"
              and "host_reads" in e["attrs"]]
    return sum(counts) / len(counts) if counts else None
