"""Every traffic mix: deterministic from the seed, on its grid, inside
its caps, and the same multiset of sizes for every seed.  A new mix (and
a new cell naming it) is found by name, with no code edited."""

import collections
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import harness, traffic

MIXES = sorted(p.stem for p in traffic.MIXES.glob("*.json"))
BENCH = Path(harness.BENCH)
SEEDS = (3, 2**31 + 11)


def shapes_of(t):
    return collections.Counter(
        (it.tenant, tuple((x.user, x.out, x.sampled) for x in it.turns))
        for it in t.items)


def sizes_of(t):
    return collections.Counter(
        tuple((x.user, x.out, x.sampled) for x in it.turns) for it in t.items)


@pytest.mark.parametrize("name", MIXES)
def test_mix_is_deterministic_from_the_seed(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, SEEDS[1], 1000, 20)
    b = traffic.generate(mix, SEEDS[1], 1000, 20)
    assert shapes_of(a) == shapes_of(b)
    assert [it.start_s for it in a.items] == [it.start_s for it in b.items]
    it = a.items[0]
    assert (a.user_tokens(it, 0) == b.user_tokens(it, 0)).all()


@pytest.mark.parametrize("name", MIXES)
def test_mix_lengths_on_grid_and_inside_caps(name):
    mix = traffic.load_mix(name)
    t = traffic.generate(mix, SEEDS[0], 1000, 40)
    grid = mix["grid"]
    for it in t.items:
        lens = t.prompt_lengths(it)
        for P, turn in zip(lens, it.turns):
            assert P % grid == 0
            assert turn.out % grid == 0
            assert P + turn.out <= mix["max_context"]
            lo, hi = traffic.length_range(mix["output_tokens"])
            assert lo <= turn.out <= hi
        assert (len(it.turns) == 1) == ("sessions" not in mix)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_asks_for_the_same_sizes(name):
    mix = traffic.load_mix(name)
    if mix["loop"] == "open":
        a, b = (traffic.generate(mix, s, 1000, 40) for s in SEEDS)
        assert sizes_of(a) == sizes_of(b)
        gaps = traffic.exp_quantiles(len(a.items), 1.0)
        for t in (a, b):
            marks = sorted(traffic.expected_arrivals(mix, it.start_s)
                           for it in t.items)
            assert all(np.isclose(gaps, g).any() for g in np.diff(marks))
    else:
        # closed loops consume a prefix: each block of levels is whole,
        # and the pairs of prompt and answer in it are the same
        k = mix["prompt_tokens"]["levels"]
        pairs = []
        for s in SEEDS:
            t = traffic.generate(mix, s, 1000, 40)
            first = sorted(it.turns[0].user for it in t.items[:k])
            assert first == sorted(traffic.length_levels(
                mix["prompt_tokens"], mix["grid"]))
            pairs.append(sorted((it.turns[0].user, it.turns[0].out)
                                for it in t.items[:4 * k]))
        assert pairs[0] == pairs[1]


def test_seeds_reorder_only_inside_blocks():
    rng = np.random.default_rng(2**40 + 3)
    order = traffic.block_order(23, 5, rng)
    assert sorted(order) == list(range(23))
    assert all(set(order[i:i + 5]) == set(range(i, min(i + 5, 23)))
               for i in range(0, 23, 5))
    vals = traffic.exp_quantiles(40, 1.0)
    sums = [traffic.strided(vals, 5, np.random.default_rng(s))
            .reshape(8, 5).sum(1) for s in (1, 2**35)]
    assert np.allclose(sorted(sums[0]), sorted(sums[1]))
    assert np.ptp(sums[0]) < 0.5 * sums[0].mean()


def test_warm_plan_is_the_same_for_every_seed():
    cell = harness.load_cell("qwen2.5-32b-4l.sessions")
    from bench import config
    s = config.shapes_from(cell.raw)
    plans = [harness.warm_plan(traffic.generate(cell.mix, seed, s.vocab, 40),
                               s)[0] for seed in SEEDS]
    assert plans[0] == plans[1]


#: a mix that no existing file has: rate bursts (4x for 2 s in every
#: 10), one prompt in ten drawn from a long component, and lengths off
#: any grid, with a seed-drawn jitter
BURSTY = {
    "loop": "open", "rate_per_s": 2.0, "shape_seed": 0,
    "rate_profile": [[8.0, 1.0], [2.0, 4.0]],
    "prompt_tokens": {"components": [
        {"weight": 9, "median": 300, "sigma": 0.8, "min": 32, "max": 2000,
         "levels": 8},
        {"weight": 1, "median": 10000, "sigma": 0.3, "min": 8192,
         "max": 16000, "levels": 8}], "jitter": 50},
    "output_tokens": {"median": 128, "sigma": 0.5, "min": 16, "max": 512,
                      "levels": 4, "jitter": 7},
    "grid": 1, "max_context": 17000, "sampled_share": 0.0,
}


def test_a_new_mix_file_is_found_by_name(tmp_path):
    root = tmp_path
    for sub in ("configs", "mixes", "limits", "families"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "mixes" / "bursty.json").write_text(json.dumps(BURSTY))
    conf = spec["configs"][0]["name"]
    name = f"{conf}.bursty"
    (root / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps({"logit_gap": 0.1}))
    spec["workloads"].append({"name": name, "config": conf,
                              "traffic": "bursty", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(name, root)
    assert cell.mix == BURSTY and cell.limits == {"logit_gap": 0.1}
    t = traffic.generate(cell.mix, 2**31 + 1, 1000, 50)
    # 5 periods of 8 s at 2/s and 2 s at 8/s: 160 starts, half in bursts
    starts = np.array([it.start_s for it in t.items])
    assert len(starts) == 160 and starts.max() < 50
    in_burst = (starts % 10.0) >= 8.0
    assert in_burst.sum() == pytest.approx(80, abs=3)
    prompts = [it.turns[0].user for it in t.items]
    assert sum(p >= 8192 for p in prompts) == pytest.approx(16, abs=2)
    assert len(set(prompts)) > 24           # jittered off the levels
    other = traffic.generate(cell.mix, 5, 1000, 50)
    assert {it.turns[0].user for it in other.items} != set(prompts)
    from bench import config
    keys, _ = harness.warm_plan(t, config.shapes_from(cell.raw, root))
    assert {k[1] for k in keys} == set(prompts)
