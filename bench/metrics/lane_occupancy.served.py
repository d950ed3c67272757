"""Share of lane-supersteps that took a hop in the service above its
capacity: ``WalkStats.steps / slot_steps`` over the window (moves
``served_steps_per_s``)."""


def read(run):
    c = run["counters"]
    if c["slot_steps"] <= 0:
        return None
    return 100.0 * c["steps"] / c["slot_steps"]
