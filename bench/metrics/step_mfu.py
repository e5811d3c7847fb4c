"""Device, whole step: the model FLOPs done in the traced window (prompt
tokens prefilled, with their attention over the cached prefix and the
head on the last token; decode rows with attention and the head) over
the traced window times the chip's bf16 peak."""

from bench import flops
from bench import trace as tracing


def read(record):
    peak = tracing.peak(record)
    t = record["trace"]
    if peak is None or not t["window_s"]:
        return None
    s = record["shapes"]
    work = sum(flops.decode_flops(s, st.lens)
               for st in tracing.traced_steps(record))
    work += sum(flops.prefill_flops(s, start, n)
                for start, n in tracing.traced_prefills(record))
    return 100.0 * work / (t["window_s"] * peak["bf16_flops"])
