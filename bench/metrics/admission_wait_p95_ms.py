"""95th percentile of the wait between a request's submission and its
admission into the service's slot ring (``WalkRequest.wall_admitted -
wall_submitted``), over the answered requests due in the window."""

from bench.stats import nearest_rank


def read(run):
    req = run["requests"]
    if req is None or req["admission_wait_s"].size == 0:
        return None
    return 1e3 * nearest_rank(req["admission_wait_s"], 0.95)
