"""Spans inside the serving engine.

``ServingEngine`` times each phase of a step with ``TelemetrySink.span``:
always-on totals that ``serving_stats()`` and ``/metrics`` carry, and a
``jax.profiler`` annotation on the trace's clock.  These tests run a
reduced transformer through ``Server`` on the CPU and check the totals'
invariants, the spans' nesting and metadata in a profiler capture, and
the exported counter families.
"""

import glob

import jax
import numpy as np
import pytest

from repro.core.telemetry import TelemetrySink
from repro.runtime import Request, Server, ServerConfig
from repro.runtime.serve_loop import SPANS

CHILDREN = [s for s in SPANS if s not in ("serve.submit", "serve.step")]


def _requests(rng, vocab, first_id, n=3):
    return [
        Request(prompt=rng.integers(0, vocab, (5 + 3 * i,)).astype(np.int32),
                max_new_tokens=3, request_id=first_id + i)
        for i in range(n)
    ]


def _serve(srv, reqs):
    """Submit and step until idle; returns how many times step() ran."""
    for r in reqs:
        srv.submit(r)
    steps = 0
    while srv.engine.has_work():
        srv.step()
        steps += 1
    return steps


@pytest.fixture(scope="module")
def served():
    from repro.configs import get_reduced
    from repro.models import build_model

    cfg = get_reduced("qwen2.5-32b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    srv = Server(model, params, ServerConfig(max_batch=2, max_seq=32))
    rng = np.random.default_rng(0)
    reqs = _requests(rng, cfg.vocab_size, 0)
    steps = _serve(srv, reqs)
    yield srv, cfg, rng, steps, len(reqs)
    srv.close()


def test_span_totals_fill_every_engine_span(served):
    srv, _, _, steps, submitted = served
    stats = srv.engine.serving_stats()
    secs, calls = stats["span_seconds_total"], stats["span_calls_total"]
    assert set(secs) == set(calls) == set(SPANS)
    for name in SPANS:
        assert calls[name] > 0 and secs[name] > 0.0, name
    assert calls["serve.step"] == steps
    assert calls["serve.submit"] == submitted
    assert calls["serve.sample"] == stats["decode_steps_total"]
    assert sum(secs[c] for c in CHILDREN) <= secs["serve.step"]


def test_spans_nest_under_the_caller_in_a_profiler_trace(served, tmp_path):
    srv, cfg, rng, _, _ = served
    reqs = _requests(rng, cfg.vocab_size, 100, n=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            _serve(srv, reqs)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = [
        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
         dict(ev.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for ev in line.events
    ]
    (caller,) = [e for e in events if e[0] == "caller"]
    steps = [e for e in events if e[0] == "serve.step"]
    assert steps
    assert all(caller[1] <= a and b <= caller[2] for _, a, b, _ in steps)
    prefills = [e for e in events if e[0] == "serve.prefill"]
    assert {meta["req"] for *_, meta in prefills} == {100, 101}
    assert all(meta["tokens"] > 0 for *_, meta in prefills)
    for name, a, b, _ in events:
        if name in CHILDREN:
            assert any(s <= a and b <= e for _, s, e, _ in steps), name


def test_metrics_render_the_span_families(served):
    srv = served[0]
    text = srv.metrics.render()
    assert "# TYPE seepp_serving_span_seconds_total counter" in text
    assert 'seepp_serving_span_seconds_total{span="serve.sample"}' in text
    dump = srv.dump_metrics()
    calls = dump["seepp_serving_span_calls_total"]
    stats = srv.engine.serving_stats()["span_calls_total"]
    assert calls['{span="serve.step"}'] == stats["serve.step"]


def test_sink_span_counts_a_raising_body_and_clear_resets():
    sink = TelemetrySink()
    with sink.span("a", req=1):
        pass
    with pytest.raises(ValueError):
        with sink.span("a"):
            raise ValueError("boom")
    seconds, calls = sink.span_totals()["a"]
    assert calls == 2 and seconds >= 0.0
    sink.clear()
    assert sink.span_totals() == {}
