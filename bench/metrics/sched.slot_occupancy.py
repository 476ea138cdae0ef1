"""Occupied slot-steps over slots x decode steps, in the window (the
scheduler's own counters)."""


def read(run):
    c = run.counters
    total = c["slots"] * c["decode_steps"]
    return 100.0 * c["occupied_slot_steps"] / total if total else None
