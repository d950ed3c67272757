"""95th percentile of how late the load generator sent a request past its
due time, over the requests due in the window: a starved generator must
not read as a fast service."""

from bench.stats import nearest_rank


def read(run):
    req = run["requests"]
    if req is None or req["generator_late_s"].size == 0:
        return None
    return 1e3 * nearest_rank(req["generator_late_s"], 0.95)
