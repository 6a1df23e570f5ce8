"""Tokens the user's job completed over the window: the tokens of every
request that started before the deadline (generated tokens, for serve
traffic), over the time from the window's opening to the end of the last
of them."""

UNIT, BETTER = "tokens/s", "higher"


def value(run):
    tokens = sum(r.units for r in run.requests if r.ok)
    return tokens / (run.t_end - run.t0) if tokens else None
