"""Share of lane-supersteps that took a hop: ``WalkStats.steps /
slot_steps`` over the window."""


def read(run):
    c = run["counters"]
    if c["slot_steps"] <= 0:
        return None
    return 100.0 * c["steps"] / c["slot_steps"]
