"""Scheduler (``serving/engine.py`` ``SlotServer``): the share of decode
slot-steps in the window that carried a live request."""
UNIT = "fraction"
LAYER = "scheduler"
MOVES = "out_tok_s"


def read(run):
    steps = run.delta("decode_steps")
    if not steps:
        return None
    return run.delta("occupied_slot_steps") / (steps * run.n_slots)
