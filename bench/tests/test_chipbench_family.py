"""A model family is new files only: a toy family written into a
temporary root, next to a configuration that names it, runs a cell end
to end on the CPU and comes out correct; the same toy with its reference
missing a step reads above the limit; a configuration that names an
absent family fails with the path it looked for."""

import json
import re
import time

import pytest

from bench import config, harness
from bench.tests import test_chipbench_rehearsal as rehearsal

CELL = "toy.chat"
#: the dense decoder with RMSNorm on each head's q and k before RoPE
#: (``ArchConfig(qk_norm=True)``, which the program serves).  q and k are
#: drawn four times wider than dense draws them, the growth that
#: qk-norm is there to tame, so a reference without it reads far off.
TOY = '''
import dataclasses
import math

from bench.families import dense
from bench.reference import _mm, _rms

QK_NORM = {qk_norm}

shapes = dense.shapes
matmul_params_per_token = dense.matmul_params_per_token


def arch_config(s):
    return dataclasses.replace(dense.arch_config(s), qk_norm=True)


def layout(s):
    tree = dense.layout(s)
    tree["layers"]["attn"].update(q_norm=(s.layers, s.head_dim),
                                  k_norm=(s.layers, s.head_dim))
    return tree


def scale(path, shape, s):
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("q_norm", "k_norm"):
        return 1.0, 0.1
    if leaf in ("wq", "wk"):
        return 0.0, 4.0 / math.sqrt(s.d_model)
    return dense.scale(path, shape, s)


def layer(s, lower, x, p):
    a = _rms(x, p["ln1"], s.norm_eps)
    q, k, v = dense.qkv(s, lower, a, p)
    if QK_NORM:
        q = _rms(q, p["attn"]["q_norm"], s.norm_eps)
        k = _rms(k, p["attn"]["k_norm"], s.norm_eps)
    o = dense.attend(s, lower, q, k, v)
    x = x + _mm("sf,fd->sd", o, p["attn"]["wo"], lower)
    return dense.mlp(s, lower, x, p)
'''


def make_root(root, family: str, source: str = None):
    """A root holding one cell of the rehearsal's traffic over a config
    that names ``family``; ``source`` is written as that family's file."""
    for sub in ("configs", "mixes", "limits", "families"):
        (root / "bench" / sub).mkdir(parents=True)
    if source is not None:
        (root / "bench" / "families" / f"{family}.py").write_text(source)
    raw = dict(rehearsal.SMOKE, name="toy",
               arch=dict(rehearsal.SMOKE["arch"], family=family))
    (root / "bench/configs/toy.json").write_text(json.dumps(raw))
    (root / "bench/mixes/chat.json").write_text(json.dumps(rehearsal.CHAT))
    (root / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"logit_gap": rehearsal.LIMIT}))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "toy", "source": "test",
                        "file": "bench/configs/toy.json", "reduced": []}]
    spec["workloads"] = [{"name": CELL, "config": "toy", "traffic": "chat",
                          "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, seed):
    return harness.run_cell(CELL, seed, 1.5, False,
                            t_start=time.perf_counter(), root=root,
                            on_chip=False, out_dir=root / "traces")


def repo_files():
    return sorted(str(p) for p in harness.BENCH.rglob("*")
                  if "__pycache__" not in p.parts)


def test_a_family_from_files_alone_runs_correct(tmp_path):
    before = repo_files()
    root = make_root(tmp_path, "toy_qknorm", TOY.format(qk_norm=True))
    s = config.shapes_from(json.loads(
        (root / "bench/configs/toy.json").read_text()), root)
    assert s.family == str(root / "bench/families/toy_qknorm.py")
    assert config.family(s).arch_config(s).qk_norm
    line = run(root, 2**31 + 11)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["logit_gap"]["value"] <= rehearsal.LIMIT
    assert repo_files() == before


def test_the_toy_reference_without_qk_norm_reads_above_the_limit(tmp_path):
    root = make_root(tmp_path, "toy_qknorm", TOY.format(qk_norm=False))
    line = run(root, 2**31 + 11)
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > rehearsal.LIMIT


@pytest.mark.parametrize("named", ["absent", None])
def test_a_config_naming_no_family_file_fails_with_its_path(tmp_path, named):
    root = make_root(tmp_path, named or "dense")
    raw = json.loads((root / "bench/configs/toy.json").read_text())
    if named is None:
        del raw["arch"]["family"]
    want = str(config.family_file(named or "<family>", root))
    with pytest.raises((FileNotFoundError, ValueError), match=re.escape(want)):
        config.shapes_from(raw, root)
    if named:
        with pytest.raises(FileNotFoundError, match="absent.py"):
            run(root, 1)


def test_every_configuration_of_the_benchmark_names_its_family():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for conf in spec["configs"]:
        raw = json.loads((harness.ROOT / conf["file"]).read_text())
        path = config.family_file(raw["arch"]["family"])
        assert path.is_file()
        assert config.shapes_from(raw).family == str(path)

