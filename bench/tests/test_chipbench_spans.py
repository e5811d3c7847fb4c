"""The per-layer readers over the engine's span totals: host ms per
step, per admission and per decode step's sampling, from the window's
deltas of ``serving_stats()``; ``None`` where the denominator is zero or
the program has no spans; above zero in the CPU rehearsal's traced
line."""

import pytest

from bench import harness
from bench.tests.test_chipbench_rehearsal import root, run  # noqa: F401

READERS = ("step_host_ms", "admit_host_ms", "sample_host_ms")


def stats(step=0.0, fetch=0.0, admit=0.0, prefill=0.0, arena=0.0,
          sample=0.0, steps=0, decode_steps=0, admitted=None):
    secs = {"serve.step": step, "serve.fetch": fetch, "serve.admit": admit,
            "serve.prefill": prefill, "serve.arena_sample": arena,
            "serve.sample": sample}
    return {"span_seconds_total": secs,
            "span_calls_total": {"serve.step": steps},
            "decode_steps_total": decode_steps,
            "admitted_total": admitted or {}}


def record(s0, s1):
    return {"stats0": s0, "stats1": s1}


def test_readers_read_the_window_deltas():
    s0 = stats(step=1.0, fetch=0.5, admit=0.1, prefill=0.2, arena=0.3,
               sample=0.05, steps=10, decode_steps=9, admitted={"t0": 2})
    s1 = stats(step=3.0, fetch=1.5, admit=0.2, prefill=0.6, arena=0.4,
               sample=0.25, steps=30, decode_steps=29,
               admitted={"t0": 4, "t1": 2})
    rec = record(s0, s1)
    # (2.0 - 1.0) s of host time over 20 steps
    assert harness.load_reader("step_host_ms")(rec) == pytest.approx(50.0)
    # (0.1 + 0.4 + 0.1) s over 4 admissions
    assert harness.load_reader("admit_host_ms")(rec) == pytest.approx(150.0)
    # 0.2 s over 20 decode steps
    assert harness.load_reader("sample_host_ms")(rec) == pytest.approx(10.0)


@pytest.mark.parametrize("name", READERS)
def test_a_zero_denominator_reads_none(name):
    s = stats(step=1.0, fetch=0.5, admit=0.1, sample=0.1, steps=5,
              decode_steps=5, admitted={"t0": 1})
    assert harness.load_reader(name)(record(s, s)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_none(name):
    s = {"decode_steps_total": 5, "admitted_total": {"t0": 1}}
    assert harness.load_reader(name)(
        record(s, {"decode_steps_total": 9, "admitted_total": {"t0": 3}})
    ) is None


def test_readers_read_above_zero_in_the_traced_rehearsal(root):  # noqa: F811
    line = run(root, 29, trace=True)
    for name in READERS:
        assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"][name]["unit"] == "ms"
