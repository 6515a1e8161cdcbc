"""Online Alg. 2 (``core/augmentation.py``, ``kernels/affine_warp.py``):
device milliseconds per round of the warp kernel, summed over its events
in the profiler window. Moves ``round_s``.

The warp is the one Mosaic kernel on the default round path, and the
program gives it no name of its own yet: the reader takes the device
operations whose name or trace statistics carry ``WARP_MATCH``. On a TPU
v5e the trace shows it as ``%resample.1 = f32[10,5,2700,1,1024]
custom-call(...), custom_call_target="tpu_custom_call"`` (the jitted
``kernels/affine_warp.py:resample``, batched over rows and slots)."""

WARP_MATCH = "tpu_custom_call"


def warp_seconds(trace: dict) -> float | None:
    ops = [n for n in trace["op_seconds"]
           if WARP_MATCH in n or WARP_MATCH in trace["op_stats"].get(n, "")]
    return sum(trace["op_seconds"][n] for n in ops) if ops else None


def read(run):
    s = warp_seconds(run.trace)
    return None if not s else 1e3 * s / run.rounds
