"""Kernel: the least time the paged-attention kernel could take for the
decode steps in the traced window, over its time in the trace.  The
bound is memory: every live page of K and V read whole, plus q and the
output, over the chip's HBM bandwidth (``flops.paged_attn_bytes`` from
the lengths each step served)."""

from bench import flops
from bench import trace as tracing


def read(record):
    kernel_s = record["trace"]["kernel_s"]
    peak = tracing.peak(record)
    steps = tracing.traced_steps(record)
    if not kernel_s or peak is None or not steps:
        return None
    s = record["shapes"]
    lens = [n for st in steps for n in st.lens]
    least = flops.least_seconds(flops.paged_attn_flops(s, lens),
                                flops.paged_attn_bytes(s, lens), peak)
    return 100.0 * least / kernel_s
