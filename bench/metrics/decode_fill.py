"""Scheduler: mean over the window's engine steps of the decode rows in use
over the engine's slots, in percent."""


def read(run):
    fills = [s.decode_rows / s.max_slots for steps in run.steps.values() for s in steps]
    return 100.0 * sum(fills) / len(fills) if fills else None
