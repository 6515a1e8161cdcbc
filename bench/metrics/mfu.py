"""The whole round program: the training FLOPs the round needs, over the
wall time of a round, over the chip's peak. Needed FLOPs are 3 x the
forward FLOPs of a sample (``bench/counting.py``, from the layer table)
times the real samples the round trained (the masked-in slots of its
rows, ``run.profile_slots``) times E x E_m; padded slots, the warp,
biases, activations, pooling and dropout do not count. The wall time is
the profiler window's, per round (host clock, ending in
``block_until_ready``). Moves ``round_s``."""


def read(run):
    if not run.profile_slots or run.profile_wall_s <= 0:
        return None
    t = run.traffic
    real = sum(r for r, _ in run.profile_slots) / len(run.profile_slots)
    flops = run.counting.train_flops(run.cfg) * real * t["epochs"] \
        * t["mediator_epochs"]
    per_round = run.profile_wall_s / run.rounds
    return 100.0 * flops / per_round / run.peaks["flops"]
