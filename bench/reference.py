"""Plain reference forward and the comparison that decides ``correct``.

The reference is the published decoder written out in ``jax.numpy`` in
float32 at ``precision="highest"``: the embedding, the configuration's
family's ``layer`` (``bench/families/``) scanned over the layers, a
final RMSNorm and the head.  For the dense family a layer is RMSNorm,
q/k/v with their biases, rotary embedding on the two halves of each
head, grouped-query causal attention and the MLP (SwiGLU, or the plain
two-matrix GELU-tanh MLP).  It takes the weights that
``bench.weights.make`` draws again from the seed, never the program's,
and imports nothing of the program.  Attention runs in blocks of
queries and the head only at the served positions, so one stream of
4096 tokens fits beside the weights on one chip.

The comparison: at every position whose token the program served
greedily, the gap by which the served token's reference logit lies
below the reference's best logit there.  The run's number is the widest
such gap over the sampled requests.

The control runs the same forward with every matrix product taken in
fp8 (e4m3, one scale per tensor), the step below the configuration's
bfloat16, and reads at each served position the gap of the token that
the fp8 forward puts first.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import config
from bench.config import Shapes

QUERY_BLOCK = 512
HEAD_BLOCK = 256
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to fp8 with one scale for the tensor; returned in bf16,
    which holds every fp8 value exactly."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return ((x / scale).astype(F8).astype(jnp.bfloat16),
            scale.astype(jnp.float32))


def _mm(spec: str, a, b, lower: bool):
    if lower:
        a8, sa = _q8(a)
        b8, sb = _q8(b)
        return jnp.einsum(spec, a8, b8,
                          preferred_element_type=jnp.float32) * (sa * sb)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv              # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "lower"))
def hidden(weights, tokens, *, s: Shapes, lower: bool = False):
    """Final normed hidden state of every position, float32; ``tokens``
    length a multiple of ``QUERY_BLOCK`` (padding at the end is causal
    and harms no earlier position)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    layer = config.family(s).layer

    def body(x, p):
        return layer(s, lower, x, p), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    return _rms(x, weights["final_norm"], s.norm_eps)


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _head_gaps(weights, h_ref, h_ctl, served, *, s: Shapes, control: bool):
    """Per row: best reference logit minus the served token's, and minus
    that of the token the control puts first (zeros without it)."""
    table = weights.get("unembed", weights["embed"])
    ref = _mm("rd,vd->rv", h_ref, table, False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if not control:
        return best - got, jnp.zeros_like(best)
    ctl = _mm("rd,vd->rv", h_ctl, table, True)
    pick = jnp.argmax(ctl, axis=-1)
    ctl_got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best - got, best - ctl_got


def consumed_stream(prompt: Sequence[int], tokens: Sequence[int]) -> np.ndarray:
    """What the engine's model consumed to serve ``tokens``: the prompt,
    the last prompt token once more (the first decode step's input),
    then every served token but the last."""
    return np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray([prompt[-1]], np.int32),
                           np.asarray(tokens[:-1], np.int32)])


def gaps(weights, s: Shapes, prompt, tokens, *, control: bool = False
         ) -> Tuple[np.ndarray, np.ndarray]:
    """(served gaps, control gaps) at each served position of one
    request; the control gaps are empty unless ``control``."""
    stream = consumed_stream(prompt, tokens)
    n = len(stream)
    padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    toks = np.zeros(padded, np.int32)
    toks[:n] = stream
    first = len(prompt)                     # row that serves tokens[0]
    with jax.default_matmul_precision("highest"):
        h_ref = hidden(weights, jnp.asarray(toks), s=s)
        h_ctl = (hidden(weights, jnp.asarray(toks), s=s, lower=True)
                 if control else h_ref)
        served, ctl = [], []
        for b in range(first, n, HEAD_BLOCK):
            rows = np.arange(b, b + HEAD_BLOCK)
            rows = np.minimum(rows, n - 1)
            want = np.asarray(tokens, np.int32)[rows - first]
            g, c = _head_gaps(weights, h_ref[rows], h_ctl[rows],
                              jnp.asarray(want), s=s, control=control)
            keep = min(HEAD_BLOCK, n - b)
            served.append(np.asarray(g)[:keep])
            ctl.append(np.asarray(c)[:keep])
    served = np.concatenate(served)
    return served, (np.concatenate(ctl) if control else np.zeros(0))


def widest(per_request: List[np.ndarray]) -> float:
    return float(max(float(np.max(g)) for g in per_request if g.size))
