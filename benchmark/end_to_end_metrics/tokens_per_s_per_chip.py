"""Tokens of a block of ``log_every`` completed steps over the block's
median seconds, fetch to fetch, over the chips (host clock)."""


def read(run):
    return run.rate_per_chip("tokens")
