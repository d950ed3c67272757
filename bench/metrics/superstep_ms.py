"""Device time of the closed-batch engine program (``jit_run``, the
superstep ``while_loop`` of ``Walker.run``) per superstep it ran
(``WalkStats.supersteps`` over the window)."""

ENGINE = "jit_run"


def read(run):
    engine_s = run["trace"].program_s(ENGINE)
    supersteps = run["counters"]["supersteps"]
    if engine_s is None or supersteps <= 0:
        return None
    return 1e3 * engine_s / supersteps
