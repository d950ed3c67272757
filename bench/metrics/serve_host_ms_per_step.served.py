"""Host time of one service step in the cell above capacity: each
``serve.step`` span in the window (admission, the chunk's dispatch and its
blocking reads, harvest) less the device busy time inside it, averaged
over the window's steps (moves ``served_steps_per_s``)."""

from bench.program_trace import ProgramTrace


def read(run):
    host = ProgramTrace.of(run).host_s("serve.step")
    return 1e3 * float(host.mean()) if host.size else None
