"""Model step: device time of the prefill programs (whole prompts and
suffixes over shared pages) per thousand prompt tokens they computed, in
the traced window."""

from bench import trace as tracing


def read(record):
    secs = sum(m["seconds"] for name, m in record["trace"]["modules"].items()
               if name.startswith("jit_paged_prefill"))
    tokens = sum(n for _, n in tracing.traced_prefills(record))
    if not secs or not tokens:
        return None
    return 1e3 * secs / (tokens / 1e3)
