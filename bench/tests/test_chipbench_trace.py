"""The reduction from a profiler trace to the per-layer numbers: on a
small hand-written trace whose answers are worked out in the comments,
and on a trace recorded on a TPU v5e, both beside this file."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(
        (DATA / "small_trace.pbtxt").read_text())
    return trace.reduce_profile(pd)


def test_window_and_busy_time(reduced):
    # window 1000..11000 ns; ops [2000,4000) [3000,5000) [7000,8000)
    # [10500,12000): union inside the window 3000 + 1000 + 500 ns
    assert reduced["window_s"] == pytest.approx(10e-6)
    assert reduced["busy_s"] == pytest.approx(4.5e-6)
    assert reduced["devices"] == 1


def test_modules_by_name_without_program_id(reduced):
    mods = reduced["modules"]
    assert mods["jit_paged_decode_step"]["count"] == 2
    assert mods["jit_paged_decode_step"]["seconds"] == pytest.approx(3e-6)
    assert mods["jit_paged_prefill_at"]["count"] == 1


def test_kernel_time_from_the_kernel_ops(reduced):
    # fusion.9 reads the kernel's output and must not count as kernel
    assert reduced["kernel_calls"] == 1
    assert reduced["kernel_s"] == pytest.approx(1e-6)


def test_idle_gaps_labelled_by_host_span(reduced):
    gaps = dict((round(s * 1e9), label) for label, s in reduced["idle_gaps"])
    # idle: [1000,2000) in bench.submit, [5000,7000) in bench.step,
    # [8000,10500) in bench.wait
    assert gaps == {1000: "bench.submit", 2000: "bench.step",
                    2500: "bench.wait"}
    assert reduced["idle_gaps"][0][0] == "bench.wait"


def test_top_ops(reduced):
    names = [n for n, _ in reduced["top_ops"]]
    assert set(names) == {"fusion.1", "fusion.2", "paged_attention_pallas.7",
                          "fusion.9"}


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e: a 2-layer model served through
    ``Server`` (4 requests, 24 decode steps), its window marked by a
    ``bench.window`` host span."""
    import jax
    pd = jax.profiler.ProfileData.from_file(
        str(DATA / "serve_probe.xplane.pb"))
    old = trace.WINDOW_SPAN
    trace.WINDOW_SPAN = "bench.window"
    try:
        return trace.reduce_profile(pd)
    finally:
        trace.WINDOW_SPAN = old


def test_recorded_trace_modules_and_kernel(recorded):
    decode = recorded["modules"]["jit_paged_decode_step"]
    assert decode["count"] == 24
    # one kernel call per layer per decode step
    assert recorded["kernel_calls"] == 2 * decode["count"]
    assert 0 < recorded["kernel_s"] < decode["seconds"]
    assert recorded["top_ops"][0][0].startswith("paged_attention_pallas")


def test_recorded_trace_busy_is_the_modules_time(recorded):
    modules = sum(m["seconds"] for m in recorded["modules"].values())
    assert recorded["busy_s"] <= recorded["window_s"]
    assert recorded["busy_s"] == pytest.approx(modules, rel=0.05)
    # the loop around each step's layers is not a leaf op
    assert not any(n.startswith("while") for n, _ in recorded["top_ops"])


def test_idle_gaps_take_the_innermost_serve_span():
    import jax
    # the small trace with the program's spans inside bench.step:
    # serve.step [2500,7800) and its child serve.fetch [4800,7500); the
    # idle [5000,7000) lies in both, [1000,2000) and [8000,10500) in
    # bench spans only
    text = (DATA / "small_trace.pbtxt").read_text()
    text = text.replace(
        '    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 3000000 }\n',
        '    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 3000000 }\n'
        '    events { metadata_id: 5 offset_ps: 2500000 duration_ps: 5300000 }\n'
        '    events { metadata_id: 6 offset_ps: 4800000 duration_ps: 2700000 }\n')
    text = text.replace(
        '  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }\n',
        '  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }\n'
        '  event_metadata { key: 5 value { id: 5 name: "serve.step" } }\n'
        '  event_metadata { key: 6 value { id: 6 name: "serve.fetch" } }\n')
    red = trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(text))
    gaps = dict((round(s * 1e9), label) for label, s in red["idle_gaps"])
    assert gaps == {1000: "bench.submit", 2000: "serve.fetch",
                    2500: "bench.wait"}


def test_recorded_trace_without_serve_spans_keeps_bench_labels(recorded):
    # recorded before the program had spans: every gap inside the window
    # lies in a step, and keeps that label
    labels = {label for label, _ in recorded["idle_gaps"]}
    assert labels == {"bench.step"}
    assert len(recorded["idle_gaps"]) == trace.TOP
