"""The device: the share of the profiler window in which no operation
ran on the chip (1 - union of the device's op intervals / window). The
window holds warmed rounds with the engine's telemetry off, so no
per-round block is in it. Moves ``round_s``."""


def read(run):
    tr = run.trace
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
