"""Engine: host time of the ``serve.sample`` span (drawing every live
row's token from the logits on the host) per decode step of the
window."""


def read(record):
    s0, s1 = record["stats0"], record["stats1"]
    if "span_seconds_total" not in s1:
        return None                    # a program without spans
    steps = s1["decode_steps_total"] - s0["decode_steps_total"]
    if not steps:
        return None
    return 1e3 * (s1["span_seconds_total"]["serve.sample"]
                  - s0["span_seconds_total"]["serve.sample"]) / steps
