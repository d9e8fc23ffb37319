"""Sort-free PWL envelope algebra: merge-path vs sort-based vs oracle.

The merge-path rewrite of ``core/pwl.py`` (``merge_sorted`` +
prefix-sum ``_compact``) must be a *drop-in* for the old
sort-with-concat engine: same knot positions, same values, same end
slopes, same raw (pre-truncation) knot counts — bit for bit.  The old
implementations are retained as ``_merge_take_bysort`` /
``_compact_bysort`` precisely so these tests can run both engines on the
same inputs.  On top of that, the traced TC hot path must contain no
``sort``/``argsort`` primitive at all (the property that unblocks a
Mosaic lowering of ``kernels/rz_step.py`` and removed the dominant cost
of the CPU hot path), and the degenerate-interval slope guard of
``_eval1``/``_slope1`` must keep coincident knots NaN-free.
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pwl as P
from repro.core import pwl_ref as R
from repro.kernels.contracts import jaxpr_summary


@contextlib.contextmanager
def sort_based_engine():
    """Swap core/pwl.py back onto the pre-merge-path sort kernels.

    ``merge_sorted`` delegates to ``_merge_take`` through the module
    global, so swapping ``_merge_take`` + ``_compact`` flips every merge
    and compaction in the algebra at once.
    """
    merge, compact = P._merge_take, P._compact
    P._merge_take, P._compact = P._merge_take_bysort, P._compact_bysort
    try:
        yield
    finally:
        P._merge_take, P._compact = merge, compact


def _assert_pwl_identical(a, b, context: str):
    """Bitwise equality of two (PWL, m_raw) results (±0.0 compare equal)."""
    (fa, ma), (fb, mb) = a, b
    for xa, xb, name in zip(fa, fb, ("xs", "ys", "sl", "sr", "m")):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=f"{context}: {name} differs")
    assert int(ma) == int(mb), f"{context}: m_raw {int(ma)} != {int(mb)}"


# --------------------------------------------------------------------- #
# merge_sorted / _compact primitives
# --------------------------------------------------------------------- #
def test_merge_sorted_matches_sort_with_padding(rng):
    for _ in range(200):
        na, nb = int(rng.integers(1, 25)), int(rng.integers(1, 25))
        a = np.sort(rng.normal(0, 2, na))
        b = np.sort(rng.normal(0, 2, nb))
        # BIG padding tails of random length, plus injected duplicates
        a[int(rng.integers(0, na + 1)):] = P.BIG
        b[int(rng.integers(0, nb + 1)):] = P.BIG
        if na > 2:
            a[1] = a[0]                       # duplicate inside a
        if rng.random() < 0.5 and nb > 1:
            b = np.sort(np.concatenate([b[:-1], a[:1]]))  # dup across a/b
        got = np.asarray(P.merge_sorted(jnp.asarray(a), jnp.asarray(b)))
        want = np.sort(np.concatenate([a, b]))
        np.testing.assert_array_equal(got, want)


def test_merge_take_routes_payloads_with_ties(rng):
    """Payloads must follow their key element through the merge, with
    ties resolved a-first — identically in both engines (the property
    the payload-carrying envelope relies on)."""
    for _ in range(100):
        na, nb = int(rng.integers(1, 20)), int(rng.integers(1, 20))
        a = np.sort(rng.integers(0, 8, na)).astype(float)   # many ties
        b = np.sort(rng.integers(0, 8, nb)).astype(float)
        a[int(rng.integers(0, na + 1)):] = P.BIG
        b[int(rng.integers(0, nb + 1)):] = P.BIG
        pa, pb = 100.0 + np.arange(na), 200.0 + np.arange(nb)
        got = P._merge_take(jnp.asarray(a), jnp.asarray(b),
                            (jnp.asarray(pa), jnp.asarray(pb)))
        want = P._merge_take_bysort(jnp.asarray(a), jnp.asarray(b),
                                    (jnp.asarray(pa), jnp.asarray(pb)))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        # payload slots match their key's provenance
        key_to_payload = {**{(0, i): pa[i] for i in range(na)},
                          **{(1, j): pb[j] for j in range(nb)}}
        srcs = sorted([(a[i], 0, i) for i in range(na)]
                      + [(b[j], 1, j) for j in range(nb)])
        for k, (x, side, idx) in enumerate(srcs):
            assert float(got[0][k]) == x
            assert float(got[1][k]) == key_to_payload[(side, idx)]


def test_compact_matches_argsort_compaction(rng):
    for _ in range(200):
        n = int(rng.integers(1, 40))
        xs = np.sort(rng.normal(0, 2, n))
        xs[int(rng.integers(0, n + 1)):] = P.BIG
        ys = rng.normal(0, 50, n)
        keep = (rng.random(n) < 0.5) & (xs < P.BIG / 2)
        new = P._compact(jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(keep))
        old = P._compact_bysort(jnp.asarray(xs), jnp.asarray(ys),
                                jnp.asarray(keep))
        for a, b, name in zip(new, old, ("xs", "ys", "m")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"_compact {name}")


# --------------------------------------------------------------------- #
# envelope / cone: merge-path == sort-based == oracle
# --------------------------------------------------------------------- #
def _random_ref(rng, max_m=6):
    m = int(rng.integers(1, max_m + 1))
    xs = np.sort(rng.normal(0, 2, m)) + np.arange(m) * 0.05
    ys = rng.normal(0, 50, m)
    sl = rng.uniform(-150, -50)
    sr = rng.uniform(-100, -10)
    return R.PWLRef(xs, ys, sl, sr)


@pytest.mark.parametrize("take_max", [True, False])
def test_envelope_merge_path_equals_sort_based(rng, take_max):
    K = 16
    for _ in range(60):
        f, g = _random_ref(rng), _random_ref(rng)
        F, G = P.from_ref(f, K), P.from_ref(g, K)
        new = P.envelope2(F, G, K, take_max)
        with sort_based_engine():
            old = P.envelope2(F, G, K, take_max)
        _assert_pwl_identical(new, old, f"envelope2(take_max={take_max})")


def test_cone_merge_path_equals_sort_based(rng):
    K = 16
    for _ in range(60):
        f = _random_ref(rng)
        a = float(rng.uniform(80, 140))
        b = float(rng.uniform(20, 70))
        f.s_left = min(f.s_left, -b - 1.0)
        f.s_right = max(f.s_right, -a)
        F = P.from_ref(f, K)
        new = P.cone_infconv(F, a, b, K)
        with sort_based_engine():
            old = P.cone_infconv(F, a, b, K)
        _assert_pwl_identical(new, old, "cone_infconv")


# --------------------------------------------------------------------- #
# jaxpr: the traced TC hot path must be sort-free
# --------------------------------------------------------------------- #
def _assert_sort_free(fn, *args):
    names, _ = jaxpr_summary(jax.make_jaxpr(fn)(*args))
    sorts = sorted(n for n in names if "sort" in n)
    assert not sorts, f"sort primitives in traced hot path: {sorts}"


def test_level_step_jaxpr_has_no_sort_primitive(rng):
    from repro.core.payoff import american_put
    from repro.core.rz import rz_level_step_lanes

    K, lanes = 12, 18
    f = P.make_affine(jnp.full((lanes,), -100.0), jnp.zeros((lanes,)), K)
    params = dict(s0=jnp.float64(100.0), k=jnp.float64(0.005),
                  sig_sqrt_dt=jnp.float64(0.01), r=jnp.float64(1.0001))
    _assert_sort_free(
        lambda z: rz_level_step_lanes(
            z, jnp.float64(16.0), params, capacity=K, seller=True,
            payoff=american_put(100.0), dtype=jnp.float64), f)


def test_envelope_and_cone_jaxprs_have_no_sort_primitive():
    K = 12
    f = P.make_affine(-100.0, 0.0, K)
    g = P.make_affine(-50.0, 1.0, K)
    _assert_sort_free(lambda a, b: P.envelope2(a, b, K, True), f, g)
    _assert_sort_free(lambda a: P.cone_infconv(a, 120.0, 80.0, K), f)


# --------------------------------------------------------------------- #
# degenerate-interval slope guard (_eval1/_slope1)
# --------------------------------------------------------------------- #
def test_eval_with_coincident_knots_is_finite():
    """Exactly duplicated knots must evaluate finite everywhere."""
    K = 8
    xs = np.full((K,), P.BIG)
    ys = np.zeros((K,))
    xs[:3] = [0.0, 0.0, 1.0]
    ys[:3] = [1.0, 2.0, 3.0]
    f = P.PWL(jnp.asarray(xs), jnp.asarray(ys),
              jnp.asarray(-2.0), jnp.asarray(0.5), jnp.asarray(3, jnp.int32))
    c = jnp.asarray([-1.0, 0.0, 0.5, 1.0, 2.0])
    v = P._eval1(f, c)
    s = P._slope1(f, c)
    assert np.all(np.isfinite(np.asarray(v)))
    assert np.all(np.isfinite(np.asarray(s)))
    # right of the duplicate pair the function is the (2, y=2)→(1, y=3)
    # segment; left of it the end slope applies
    np.testing.assert_allclose(np.asarray(v), [3.0, 2.0, 2.5, 3.0, 3.5])


def test_eval_subnormal_interval_width_no_nan():
    """The recorded blow-up: w below 1e-300 with a large value jump made
    ``dy / max(w, 1e-300)`` overflow to inf, and the query at the left
    knot then produced inf * 0 = NaN *in the selected branch* before the
    guard.  The width guard must keep it finite."""
    K = 4
    tiny_gap = 5e-324                         # subnormal: 0 < w < 1e-300
    xs = np.full((K,), P.BIG)
    ys = np.zeros((K,))
    xs[:2] = [0.0, tiny_gap]
    ys[:2] = [0.0, 1e10]
    f = P.PWL(jnp.asarray(xs), jnp.asarray(ys),
              jnp.asarray(-1.0), jnp.asarray(1.0), jnp.asarray(2, jnp.int32))
    c = jnp.asarray([0.0, -1.0, 1.0])
    v = P._eval1(f, c)
    s = P._slope1(f, c)
    assert np.all(np.isfinite(np.asarray(v))), np.asarray(v)
    assert np.all(np.isfinite(np.asarray(s))), np.asarray(s)
    # batched public surface too
    fb = jax.tree.map(lambda a: a[None], f)
    assert np.isfinite(float(P.eval_at(fb, jnp.zeros((1,)))[0]))
