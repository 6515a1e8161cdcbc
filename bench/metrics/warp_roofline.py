"""Online Alg. 2 kernel: its share of the roofline. The least time the
chip needs for the warp's algorithmic work of a round, the larger of
FLOPs over peak and bytes over HBM bandwidth, over the kernel's device
time per round. The work is ``bench/counting.py:warp_work`` for every
real sample that Alg. 2 resamples in the round -- the masked-in slots of
the scheduled clients, read from the operands the engine fed the round
(``run.profile_slots``) -- and not for the padded slots, which need no
warp. Moves ``round_s``."""
from bench.metrics.warp_ms import warp_seconds


def read(run):
    s = warp_seconds(run.trace)
    if not s or not run.profile_slots:
        return None
    h, w, c = run.cfg["input_shape"]
    images = sum(r for r, _ in run.profile_slots) / len(run.profile_slots)
    work = run.counting.warp_work(h, w, c)
    least = max(images * work["flops"] / run.peaks["flops"],
                images * work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s / run.rounds)
