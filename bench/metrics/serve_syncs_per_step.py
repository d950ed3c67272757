"""Blocking device-to-host reads the service makes per step that ran a
chunk: the ``syncs`` and ``chunks`` counts ``WalkService.step`` puts on its
``serve.step`` span, summed over the window's steps (moves
``request_p50_ms``).  Two read the superstep clock around the chunk, one
fetches ``done_mask``, and each harvested request takes three more."""

from bench.program_trace import ProgramTrace


def read(run):
    return ProgramTrace.of(run).syncs_per_step()
