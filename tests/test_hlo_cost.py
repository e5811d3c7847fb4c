"""Loop-aware HLO cost analyzer vs XLA's single-visit cost analysis."""

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_loop_free_matches_xla():
    def f(a, b):
        return ((a @ b) @ b).sum()

    comp = _compile(f, jnp.ones((128, 128)), jnp.ones((128, 128)))
    mine = analyze_hlo(comp.as_text())
    xla = comp.cost_analysis()["flops"]
    assert abs(mine.flops - xla) / xla < 0.05


def test_scan_trip_multiplication():
    def g(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, None, length=12)[0].sum()

    comp = _compile(g, jnp.ones((64, 64)), jnp.ones((64, 64)))
    mine = analyze_hlo(comp.as_text())
    expect = 12 * 2 * 64 ** 3
    assert abs(mine.flops - expect) / expect < 0.05
    assert 12 in mine.while_trip_counts


def test_scan_equals_unrolled():
    w = jnp.ones((6, 32, 32))
    x = jnp.ones((8, 32))

    def scan_loss(params, x):
        h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), x, params)
        return h.sum()

    def unrolled_loss(params, x):
        h = x
        for i in range(6):
            h = jnp.tanh(h @ params[i])
        return h.sum()

    costs = []
    for f in (scan_loss, unrolled_loss):
        step = lambda p, x, f=f: jax.grad(f)(p, x).sum()
        comp = _compile(step, w, x)
        costs.append(analyze_hlo(comp.as_text()).flops)
    assert abs(costs[0] - costs[1]) / costs[1] < 0.15


def test_collectives_counted_with_groups():
    hlo = """
HloModule m

ENTRY %main (p: f32[64,128]) -> f32[64,128] {
  %p = f32[64,128]{1,0} parameter(0)
  ROOT %ar = f32[64,128]{1,0} all-reduce(%p), replica_groups=[16,16]<=[256], to_apply=%add
}
"""
    cost = analyze_hlo(hlo)
    nbytes = 64 * 128 * 4
    assert cost.collectives["all-reduce"]["count"] == 1
    assert abs(cost.wire_bytes - 2 * nbytes * 15 / 16) < 1
