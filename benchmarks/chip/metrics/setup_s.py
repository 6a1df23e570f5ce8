"""Set-up time: process start to the first timed request, compiling,
making and saving the weights, and the replicas' cold start included."""

UNIT, BETTER = "s", "lower"


def value(run):
    return run.setup_s
