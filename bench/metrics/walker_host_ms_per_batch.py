"""Host time per ``Walker.run`` call: each call's span (``walker.run``:
bind, start upload, dispatch, result assembly), less the device busy time
inside it, averaged over the calls of the window.  The loop's own fetch of
the paths is outside the span."""


def read(run):
    tr = run["trace"]
    spans = tr.span("walker.run")
    if spans.shape[0] == 0:
        return None
    host = [(e - s) * 1e-9 - tr.busy_in(s, e) for s, e in spans]
    return 1e3 * sum(host) / len(host)
