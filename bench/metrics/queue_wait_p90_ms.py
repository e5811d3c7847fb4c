"""Front end: 90th percentile of the time from a request's scheduled
send to its admission into a decode slot (the engine's ``admitted_at``),
over the requests of the window."""

from bench import stats


def read(record):
    w = record["window"]
    waits = [(r.req.admitted_at - (r.sched + w["wall0"])) * 1e3
             for r in w["recs"] if w["t0"] <= r.sched < w["end"]
             and r.req.admitted_at is not None]
    return stats.percentile(waits, 90)
