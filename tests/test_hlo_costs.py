"""Loop-aware HLO cost walker — calibration against hand-counted
programs (the dry-run roofline depends on these semantics)."""
import jax
import jax.numpy as jnp
from jax import lax

from repro.launch.hlo_costs import loop_aware_costs


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


X = jnp.ones((64, 64))
W = jnp.ones((8, 64, 64))
MM = 2 * 64 ** 3  # one 64³ matmul


def test_scan_body_times_trip_count():
    def scanned(x, w):
        return lax.scan(lambda h, wi: (h @ wi, None), x, w)[0]

    def unrolled(x, w):
        h = x
        for i in range(8):
            h = h @ w[i]
        return h

    c_scan = loop_aware_costs(_hlo(scanned, X, W))
    c_unr = loop_aware_costs(_hlo(unrolled, X, W))
    assert c_scan.flops == c_unr.flops == 8 * MM


def test_nested_scans_multiply():
    w2 = jnp.ones((4, 8, 64, 64))

    def nested(x, w2):
        def outer(h, ws):
            return lax.scan(lambda h2, wi: (h2 @ wi, None), h, ws)[0], None
        return lax.scan(outer, x, w2)[0]

    assert loop_aware_costs(_hlo(nested, X, w2)).flops == 32 * MM


def test_cond_takes_max_branch():
    def f(p, x, w):
        return lax.cond(p > 0, lambda: (x @ w[0]) @ w[1],
                        lambda: x @ w[0])

    c = loop_aware_costs(_hlo(f, jnp.int32(1), X, W))
    assert c.flops == 2 * MM  # not 3·MM (sum) — one branch runs


def test_xla_cost_analysis_undercounts_loops():
    """Document the raw behaviour our walker corrects."""
    def scanned(x, w):
        return lax.scan(lambda h, wi: (h @ wi, None), x, w)[0]

    raw = jax.jit(scanned).lower(X, W).compile().cost_analysis()
    # body counted once (±loop bookkeeping ops) instead of ×8
    assert float(raw["flops"]) < 1.01 * MM
