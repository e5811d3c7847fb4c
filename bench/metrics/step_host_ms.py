"""Engine: host time of one engine step, the ``serve.step`` span less
its ``serve.fetch`` child (the wait for the device and the logits
copy), mean over the steps of the window."""


def read(record):
    s0, s1 = record["stats0"], record["stats1"]
    if "span_seconds_total" not in s1:
        return None                    # a program without spans
    secs, calls = s1["span_seconds_total"], s1["span_calls_total"]
    before, calls0 = s0["span_seconds_total"], s0["span_calls_total"]
    steps = calls["serve.step"] - calls0["serve.step"]
    if not steps:
        return None
    host = (secs["serve.step"] - before["serve.step"]
            - (secs["serve.fetch"] - before["serve.fetch"]))
    return 1e3 * host / steps
