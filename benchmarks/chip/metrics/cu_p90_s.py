"""90th percentile of request latency, from submission to the whole
result on the host, over every request of the window (inclusive method of
``statistics.quantiles``).  A request of the fleet is one serve CU,
``submit_cu`` to its result."""

import statistics

UNIT, BETTER = "s", "lower"


def value(run):
    lat = [r.t_end - r.t_submit for r in run.requests if r.ok]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10, method="inclusive")[8]
