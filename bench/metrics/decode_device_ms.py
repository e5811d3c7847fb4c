"""Model step: device time of one ``jit_paged_decode_step`` call, mean
over the calls in the traced window."""


def read(record):
    mod = record["trace"]["modules"].get("jit_paged_decode_step")
    if not mod or not mod["count"]:
        return None
    return 1e3 * mod["seconds"] / mod["count"]
