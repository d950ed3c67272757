"""Blocking device-to-host reads per step that ran a chunk, in the cell
above the service's capacity (moves ``served_steps_per_s``): the
``syncs`` and ``chunks`` of the window's ``serve.step`` spans."""

from bench.program_trace import ProgramTrace


def read(run):
    return ProgramTrace.of(run).syncs_per_step()
