"""Front end: host time of admission per request admitted in the
window: the ``serve.admit``, ``serve.prefill`` and ``serve.arena_sample``
spans (expiry, prefix lookup and share, page allocation, the prefill's
host preparation and dispatch, copy-on-write, scatter, the arena poll)."""

SPANS = ("serve.admit", "serve.prefill", "serve.arena_sample")


def read(record):
    s0, s1 = record["stats0"], record["stats1"]
    if "span_seconds_total" not in s1:
        return None                    # a program without spans
    admitted = (sum(s1["admitted_total"].values())
                - sum(s0["admitted_total"].values()))
    if not admitted:
        return None
    host = sum(s1["span_seconds_total"][k] - s0["span_seconds_total"][k]
               for k in SPANS)
    return 1e3 * host / admitted
