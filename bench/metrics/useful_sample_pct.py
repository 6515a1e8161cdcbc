"""Local training (``core/fl.py`` client updates in the mediator rows):
the share of the padded slots the round program trained that held a real
(masked-in) sample, over every traced round. The program trains every
slot of every row, each client padded to the largest, so this counts the
round's wasted work. Read from the operands the engine fed each round
(the store's sample mask, the rows' client indices and the slot mask;
``run.slots`` from ``bench/run.py:slot_counts``). Moves ``round_s``."""


def read(run):
    if not run.slots:
        return None
    real = sum(r for r, _ in run.slots)
    slots = sum(n for _, n in run.slots)
    return 100.0 * real / slots
