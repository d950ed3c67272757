"""95th percentile (nearest rank) of request latency, due time to
harvest, over all requests due in the window; one never answered counts
as infinite.  The serving cell's tail, read in a traced run beside its
median: on a service bound by host work the tail of 800 requests spreads
too widely from run to run to hold a bound end to end."""

from bench.stats import nearest_rank


def read(run):
    req = run["requests"]
    if req is None or req["latency_s"].size == 0:
        return None
    return 1e3 * nearest_rank(req["latency_s"], 0.95)
