"""A closed loop of `clients` requests kept outstanding, each completion
sending the next (MLPerf Inference "Offline")."""

from harness import traffic

CELL = "serve"
SIZES = 4096        # request sizes drawn, cycled over the window


def plan(mix, seconds, seed):
    return traffic.Plan(sizes=traffic.request_sizes(mix, SIZES, seed),
                        clients=mix["clients"])
