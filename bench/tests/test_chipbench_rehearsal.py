"""A short rehearsal of the whole run on the CPU at a small size: the
harness loop through the program's ``Server``, the check against the
reference and the shape of the result line; faults planted under the
timed path turn ``correct`` false; ``run.py`` itself refuses to run
without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness

CELL = "smoke.chat"
SMOKE = {
    "name": "smoke", "source": "a small size for CPU tests",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 8192,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "arch": {"family": "dense", "mlp": "gated", "activation": "silu",
             "qkv_bias": True, "out_bias": False, "mlp_bias": False,
             "norm": "rms"},
    "serving": {"max_batch": 4, "max_seq": 256, "page_tokens": 16,
                "pool_pages": 256},
}
CHAT = {
    "loop": "open", "rate_per_s": 4.0, "shape_seed": 0,
    "tenants": {"count": 2, "zipf": 1.1, "system_tokens": 40, "warm": True},
    "sessions": {"turns": {"min": 2, "max": 3}, "think_s": 0.2},
    "prompt_tokens": {"median": 16, "sigma": 0.5, "min": 16, "max": 32,
                      "levels": 2},
    "output_tokens": {"median": 8, "sigma": 0.3, "min": 8, "max": 16,
                      "levels": 2},
    "grid": 8, "max_context": 200, "sampled_share": 0.25,
    "sampling": {"temperature": 0.7, "top_p": 0.8, "top_k": 20},
    "server": {"prefix_cache_seqs": 16},
}
#: between the program's widest gap at this size (at most 0.021 over
#: seeds 1, 2, 3, 23, 31) and the fp8 control's (at least 0.108)
LIMIT = 0.06


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke")
    for sub in ("configs", "mixes", "limits"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copytree(harness.BENCH / "families", root / "bench" / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/smoke.json").write_text(json.dumps(SMOKE))
    (root / "bench/mixes/chat.json").write_text(json.dumps(CHAT))
    (root / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"logit_gap": LIMIT}))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "smoke", "source": "test",
                        "file": "bench/configs/smoke.json", "reduced": []}]
    spec["workloads"] = [{"name": CELL, "config": "smoke",
                          "traffic": "chat", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, seed, trace=False, **kw):
    return harness.run_cell(CELL, seed, 1.5, trace, t_start=time.perf_counter(),
                            root=root, on_chip=False,
                            out_dir=root / "traces", **kw)


def test_result_line_shape(root):
    line = run(root, 2**31 + 3)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "readings", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"output_tokens_per_s", "ttft_p50_ms",
                                    "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["logit_gap"]["limit"] == LIMIT
    json.dumps(line)


def test_traced_line_has_per_layer_metrics_and_breakdown(root):
    line = run(root, 17, trace=True)
    assert list(line)[-3:] == ["breakdown", "readings", "checks"]
    assert {"queue_wait_p90_ms", "batch_occupancy", "prefix_saved_share",
            "kv_pages_peak_share", "ttft_p90_ms.sessions",
            "itl_p95_ms.sessions"} <= set(line["metrics"])
    assert line["metrics"]["prefix_saved_share"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _stale_state(runner):
    import jax
    import jax.numpy as jnp
    eng = runner.srv.engine
    step = eng._decode_paged

    def broken(params, store, last, table, pos):
        keep = jax.tree.map(jnp.copy, store)
        _, logits = step(params, store, last, table, pos)
        return keep, logits
    eng._decode_paged = broken


def _half_batch(runner):
    eng = runner.srv.engine
    step = eng._decode_paged

    def broken(params, store, last, table, pos):
        # the second half of the live rows get the first half's logits
        store, logits = step(params, store, last, table, pos)
        live = np.flatnonzero(np.asarray(table)[:, 0] >= 0)
        half = len(live) // 2
        return store, logits.at[live[half:2 * half]].set(logits[live[:half]])
    eng._decode_paged = broken


def _altered_token(monkeypatch):
    from repro.runtime import serve_loop
    sample = serve_loop.sample_token

    def broken(logits, **kw):
        tok, method = sample(logits, **kw)
        if kw.get("index", 0) == 2:
            tok = (tok + 1) % logits.shape[-1]
        return tok, method
    monkeypatch.setattr(serve_loop, "sample_token", broken)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
def test_a_fault_under_the_timed_path_is_not_correct(root, fault,
                                                     monkeypatch):
    hook = {"stale_state": _stale_state, "half_batch": _half_batch}.get(fault)
    if fault == "altered_token":
        _altered_token(monkeypatch)
    line = run(root, 23, after_setup=hook)
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > LIMIT


def test_control_reads_above_the_program(root):
    line = run(root, 31, control=True)
    assert line["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["logit_gap"] > LIMIT


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "qwen2.5-32b-4l.sessions", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=str(harness.ROOT))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
