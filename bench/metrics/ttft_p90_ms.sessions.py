"""Requests, on the host clock: the 90th percentile of the time to the
first token over the requests sent in the window, as ``end_to_end``
takes it.  Per layer where its runs spread too widely to bound it end
to end (a p90 of about fifty turns)."""

from bench import harness


def read(record):
    return harness.end_to_end(record["window"], 0.0)["ttft_p90_ms"]
