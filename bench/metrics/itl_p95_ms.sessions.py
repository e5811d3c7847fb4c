"""Requests, on the host clock: the 95th percentile of the gaps between
consecutive tokens of the requests sent in the window, as
``end_to_end`` takes it.  Per layer where its runs spread too widely to
bound it end to end."""

from bench import harness


def read(record):
    return harness.end_to_end(record["window"], 0.0)["itl_p95_ms"]
