"""Host time to harvest one finished request: each ``serve.harvest`` span
in the window (the request's paths and lengths fetched to the host, its
slots released after a ``done`` check) less the device busy time inside
it, averaged over the requests harvested in the window (moves
``request_p50_ms``)."""

from bench.program_trace import ProgramTrace


def read(run):
    host = ProgramTrace.of(run).host_s("serve.harvest")
    return 1e3 * float(host.mean()) if host.size else None
