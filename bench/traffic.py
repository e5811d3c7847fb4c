"""The one traffic generator: a mix file of parameters plus ``--seed``.

A mix (``bench/mixes/<name>.json``) fixes the *multiset* of work: how
many sessions or requests, their turn counts, prompt and output lengths
and how they pair, which turns sample, the arrival gaps and think times.
The seed only reorders that multiset, inside blocks that each hold every
level, and draws the token ids (and, where a length asks for ``jitter``,
the offsets), so every seed asks the server for the same sizes in
another order: set-up compiles the same programs, and the work of a
window, which reaches a prefix of the items, is nearly the same from
seed to seed.

The parameters a mix may set:

* ``loop``: ``open`` or ``closed``.  Open: sessions (or single-turn
  requests) start at ``rate_per_s`` on stratified exponential gaps; an
  optional ``rate_profile`` of ``[seconds, multiplier]`` segments,
  repeated through the window, shapes the rate (bursts, ramps).  The
  pool holds the sessions that start within the window.  Closed:
  ``clients`` callers each send their next request the moment the
  previous one completes, from a ``pool`` consumed in order.
* ``prompt_tokens`` / ``output_tokens``: a log-normal (``median``,
  ``sigma``, ``min``, ``max``) read at ``levels`` evenly spaced
  probabilities, or ``components``: a list of such log-normals, each
  with an integer ``weight`` (how many times its levels are repeated),
  for mixtures such as rare long prompts among short ones.  Lengths lie
  on the mix's ``grid`` (1 for any length); ``jitter`` adds a
  seed-drawn offset below it, for lengths no earlier seed asked for.
* ``sessions``: turns per session (``min``..``max``) and the mean think
  time ``think_s`` before each later turn, which extends the previous
  turn's prompt and answer.
* ``tenants``: ``count`` tenants with Zipf(``zipf``) shares of the
  sessions, each with a ``system_tokens`` system prompt that its
  sessions share; ``warm`` parks every tenant's system prompt before the
  window.
* ``max_context``, ``sampled_share`` and ``sampling`` (the knobs of the
  turns that sample; the rest are greedy), and ``server``: keyword
  arguments of the server's config.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

MIXES = Path(__file__).resolve().parent / "mixes"
_MASK = (1 << 63) - 1


def load_mix(name: str, directory: Path = MIXES) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def _lognormal_levels(spec: dict, grid: int) -> List[int]:
    nd = NormalDist()
    out = []
    for i in range(spec["levels"]):
        v = spec["median"] * math.exp(spec["sigma"] *
                                      nd.inv_cdf((i + 0.5) / spec["levels"]))
        v = grid * round(v / grid)
        out.append(int(min(max(v, spec["min"]), spec["max"])))
    return out


def length_levels(spec: dict, grid: int) -> List[int]:
    """The log-normal's quantiles at ``(i + 0.5) / levels``, on the grid
    and inside ``[min, max]``; repeats stand for equal probability.  A
    mixture repeats each component's levels ``weight`` times."""
    if "components" not in spec:
        return _lognormal_levels(spec, grid)
    return [v for c in spec["components"]
            for v in _lognormal_levels(c, grid) * c["weight"]]


def length_range(spec: dict) -> tuple:
    """The least and the largest length a spec can give."""
    parts = spec.get("components", [spec])
    return min(c["min"] for c in parts), max(c["max"] for c in parts)


def stratified(levels: List, n: int, rng: np.random.Generator) -> list:
    """``n`` draws in blocks of ``len(levels)``, each block a permutation
    of ``levels``: every prefix of the result holds each level about
    equally often."""
    out: list = []
    while len(out) < n:
        out.extend(levels[i] for i in rng.permutation(len(levels)))
    return out[:n]


def block_order(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each index only inside
    its block of ``block`` consecutive ones: every prefix that ends on
    a block boundary holds the same indices for every ``rng``."""
    return np.concatenate([i + rng.permutation(min(block, n - i))
                           for i in range(0, n, block)]).astype(int)


def strided(values, block: int, rng: np.random.Generator) -> np.ndarray:
    """``values`` reordered so that each run of ``block`` consecutive
    ones is a sample across their whole range (the sorted values dealt
    out to the runs in turn, back and forth), in an order drawn from
    ``rng`` inside each run: the runs' sums do not depend on ``rng``."""
    xs = np.sort(np.asarray(values, float))
    runs = -(-len(xs) // block)
    deal = [[] for _ in range(runs)]
    for k in range(0, len(xs), runs):
        stratum = xs[k:k + runs]
        to = range(len(stratum)) if (k // runs) % 2 == 0 else \
            range(runs - 1, runs - 1 - len(stratum), -1)
        for j, x in zip(to, stratum):
            deal[j].append(x)
    return np.concatenate([rng.permutation(np.asarray(d)) for d in deal])


def exp_quantiles(n: int, mean: float) -> np.ndarray:
    """Stratified draws of an exponential of this mean (ascending)."""
    p = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-p)


def _segments(mix: dict) -> List[tuple]:
    """``(seconds, rate)`` segments of one period of the arrival rate."""
    prof = mix.get("rate_profile") or [[1.0, 1.0]]
    return [(float(d), mix["rate_per_s"] * float(m)) for d, m in prof]


def expected_arrivals(mix: dict, t: float) -> float:
    """Mean number of starts in ``[0, t)``: the integral of the rate."""
    segs = _segments(mix)
    period = sum(d for d, _ in segs)
    per_period = sum(d * r for d, r in segs)
    whole, rest = divmod(t, period)
    out = whole * per_period
    for d, r in segs:
        out += min(d, rest) * r
        rest = max(0.0, rest - d)
    return out


def arrival_time(mix: dict, u: float) -> float:
    """The time at which ``expected_arrivals`` reaches ``u``."""
    segs = _segments(mix)
    period = sum(d for d, _ in segs)
    per_period = sum(d * r for d, r in segs)
    whole, rest = divmod(u, per_period)
    t = whole * period
    for d, r in segs:
        if rest <= d * r:
            return t + (rest / r if r > 0 else 0.0)
        rest -= d * r
        t += d
    return t


def zipf_counts(n: int, count: int, s: float) -> List[int]:
    """``n`` items over ``count`` ranks with weights 1/rank^s, by largest
    remainder, so the counts are exact and fixed."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    base = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - base), kind="stable")[: n - base.sum()]
    base[rest] += 1
    return base.tolist()


@dataclass
class Turn:
    user: int          # new prompt tokens (the whole prompt when single-turn)
    out: int           # tokens to generate
    sampled: bool      # samples with the mix's knobs; greedy otherwise


@dataclass
class Item:
    index: int
    turns: List[Turn]
    tenant: Optional[int] = None
    start_s: float = 0.0                            # open loop: first send
    think_s: List[float] = field(default_factory=list)  # before turn k>0


@dataclass
class Traffic:
    mix: dict
    seed: int
    vocab: int
    items: List[Item]

    @property
    def loop(self) -> str:
        return self.mix["loop"]

    @property
    def system_tokens(self) -> int:
        return self.mix.get("tenants", {}).get("system_tokens", 0)

    def system_prompt(self, tenant: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed & _MASK, 1, tenant])
        return rng.integers(0, self.vocab, self.system_tokens).astype(np.int32)

    def user_tokens(self, item: Item, turn: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed & _MASK, 2, item.index, turn])
        return rng.integers(0, self.vocab,
                            item.turns[turn].user).astype(np.int32)

    def prompt_lengths(self, item: Item) -> List[int]:
        """Prompt length of each turn: system prompt, then every earlier
        turn's prompt and answer, then this turn's user tokens."""
        out, ctx = [], self.system_tokens
        for t in item.turns:
            out.append(ctx + t.user)
            ctx += t.user + t.out
        return out


def _turn_shapes(mix: dict, n_items: int, rng: np.random.Generator):
    grid = mix["grid"]
    sess = mix.get("sessions")
    if sess:
        counts = stratified(list(range(sess["turns"]["min"],
                                       sess["turns"]["max"] + 1)),
                            n_items, rng)
    else:
        counts = [1] * n_items
    total = sum(counts)
    user = stratified(length_levels(mix["prompt_tokens"], grid), total, rng)
    out = stratified(length_levels(mix["output_tokens"], grid), total, rng)
    n_sampled = round(mix.get("sampled_share", 0.0) * total)
    sampled = np.zeros(total, bool)
    sampled[rng.permutation(total)[:n_sampled]] = True
    return counts, user, out, sampled.tolist()


def _block(mix: dict) -> int:
    """Items to a block in which every level of the mix's turn counts
    (sessions) or of its prompt and answer lengths occurs."""
    sess = mix.get("sessions")
    if sess:
        return sess["turns"]["max"] - sess["turns"]["min"] + 1
    return math.lcm(len(length_levels(mix["prompt_tokens"], mix["grid"])),
                    len(length_levels(mix["output_tokens"], mix["grid"])))


def _jitter(lengths: List[int], spec: dict,
            rng: np.random.Generator) -> List[int]:
    """Each length plus a seed-drawn offset below ``spec["jitter"]``,
    kept inside the spec's range."""
    j = spec.get("jitter", 0)
    if not j:
        return lengths
    lo, hi = length_range(spec)
    return [int(min(max(v + rng.integers(0, j), lo), hi)) for v in lengths]


def _fit(turns: List[Turn], system: int, max_context: int) -> List[Turn]:
    """Keep the turns whose whole context fits ``max_context``."""
    kept, ctx = [], system
    for t in turns:
        if ctx + t.user + t.out > max_context:
            break
        kept.append(t)
        ctx += t.user + t.out
    if not kept:
        raise ValueError("a first turn does not fit max_context")
    return kept


def generate(mix: dict, seed: int, vocab: int, seconds: float) -> Traffic:
    """The cell's work for a window of ``seconds`` from ``seed``."""
    shape_rng = np.random.default_rng(mix["shape_seed"])
    seed_rng = np.random.default_rng(seed & _MASK)
    system = mix.get("tenants", {}).get("system_tokens", 0)
    if mix["loop"] == "open":
        n = max(1, round(expected_arrivals(mix, seconds)))
    elif mix["loop"] == "closed":
        n = mix["pool"]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    # the shapes, and which prompt goes with which answer, are the mix's
    # alone; the seed reorders items only inside blocks that hold whole
    # sets of levels, so any window, which serves a prefix of the items,
    # meets nearly the same work whatever the seed
    counts, user, out, sampled = _turn_shapes(mix, n, shape_rng)
    order = block_order(n, _block(mix), seed_rng)

    user = _jitter(user, mix["prompt_tokens"], seed_rng)
    out = _jitter(out, mix["output_tokens"], seed_rng)
    # the first turn's user tokens are topped up so that every prompt,
    # the system prompt's included, lies on the grid
    pad = (-system) % mix["grid"]
    shapes, k = [], 0
    for c in counts:
        turns = [Turn(user[k + j] + (pad if j == 0 else 0), out[k + j],
                      sampled[k + j]) for j in range(c)]
        shapes.append(_fit(turns, system, mix["max_context"]))
        k += c

    items = [Item(index=i, turns=shapes[j]) for i, j in enumerate(order)]
    if mix["loop"] == "open":
        # stratified gaps of a unit-rate process, mapped through the
        # rate's integral: a constant rate gives exponential gaps of mean
        # 1 / rate_per_s, a profile compresses them where it is high
        gaps = strided(exp_quantiles(n, 1.0), _block(mix), seed_rng)
        marks = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for it, u in zip(items, marks):
            it.start_s = arrival_time(mix, float(u))
    sess = mix.get("sessions")
    if sess:
        later = [(it, j) for it in items for j in range(1, len(it.turns))]
        thinks = strided(exp_quantiles(len(later), sess["think_s"]),
                         _block(mix), seed_rng)
        for it in items:
            it.think_s = [0.0] * len(it.turns)
        for (it, j), th in zip(later, thinks):
            it.think_s[j] = float(th)
    ten = mix.get("tenants")
    if ten:
        tenants = np.repeat(np.arange(ten["count"]),
                            zipf_counts(n, ten["count"], ten["zipf"]))
        for it, t in zip(items, seed_rng.permutation(tenants)):
            it.tenant = int(t)
    return Traffic(mix=mix, seed=seed, vocab=vocab, items=items)
