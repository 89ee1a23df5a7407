"""Open-loop Poisson arrivals at `rate_per_s` (MLPerf Inference
"Server"): rate x seconds requests whose gaps are the quantiles of the
exponential distribution, scaled to fill the window."""

from harness import traffic

CELL = "serve"


def plan(mix, seconds, seed):
    offsets = traffic.poisson_offsets(mix["rate_per_s"], seconds, seed)
    return traffic.Plan(sizes=traffic.request_sizes(mix, len(offsets), seed),
                        offsets=offsets)
