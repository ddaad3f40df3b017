"""The grouped design of ``make_problem`` and the climate stand-in's layout.

``make_problem`` builds the (n, G, ng) design in one device computation;
``_loop_reference`` is the per-group loop it replaced, kept here as the
reference: every field must come out bitwise equal.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgl
from repro.data.climate import make_climate_like
from repro.obs import trace


def _loop_reference(X_flat, y, group_sizes, tau):
    """One eager ``.at[].set`` per group, then the same norms."""
    X_flat = jnp.asarray(X_flat)
    sizes = [int(s) for s in group_sizes]
    n, p = X_flat.shape
    G, ng = len(sizes), max(sizes)
    Xg = jnp.zeros((n, G, ng), X_flat.dtype)
    mask = jnp.zeros((G, ng), bool)
    off = 0
    for g, s in enumerate(sizes):
        Xg = Xg.at[:, g, :s].set(X_flat[:, off:off + s])
        mask = mask.at[g, :s].set(True)
        off += s
    Lg = sgl._group_spectral_norms(Xg)
    return sgl.SGLProblem(
        X=Xg, y=jnp.asarray(y, X_flat.dtype),
        w=jnp.sqrt(jnp.asarray(sizes, X_flat.dtype)),
        tau=jnp.asarray(tau, X_flat.dtype), feat_mask=mask, Lg=Lg,
        Xnorm_col=jnp.linalg.norm(Xg, axis=0), Xnorm_grp=jnp.sqrt(Lg))


@pytest.mark.parametrize("sizes", [[7] * 6, [10] * 5, [3, 7, 1, 5]],
                         ids=["groups-of-7", "groups-of-10", "ragged"])
def test_make_problem_is_bitwise_the_per_group_loop(sizes):
    rng = np.random.default_rng(len(sizes))
    n, p = 23, sum(sizes)
    X = rng.standard_normal((n, p))
    X[:, 1] = -0.0                     # a signed zero survives the copy
    y = rng.standard_normal(n)
    got = sgl.make_problem(X, y, sizes, tau=0.3)
    want = _loop_reference(X, y, sizes, tau=0.3)
    for field in sgl.SGLProblem._fields:
        a, b = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert a.tobytes() == b.tobytes(), field


def test_make_problem_rejects_sizes_that_miss_p():
    X = np.zeros((4, 10))
    with pytest.raises(ValueError, match="p=10"):
        sgl.make_problem(X, np.zeros(4), [3, 3, 3], tau=0.5)


def test_make_problem_span_and_gauge_fire_once_per_call(monkeypatch):
    sets = []

    class Recorder:
        def set(self, v):
            sets.append(v)

    monkeypatch.setattr(sgl, "_M_MAKE_PROBLEM_S", Recorder())
    was = trace.TRACER.enabled
    trace.configure(enabled=True)
    trace.TRACER.reset()
    try:
        rng = np.random.default_rng(0)
        X, y = rng.standard_normal((9, 12)), rng.standard_normal(9)
        sgl.make_problem(X, y, [4, 4, 4], tau=0.5)
        assert trace.TRACER.counts().get("make_problem") == 1
        sgl.make_problem(X, y, [5, 4, 3], tau=0.5)
        assert trace.TRACER.counts().get("make_problem") == 2
        attrs = [r["attrs"] for r in trace.TRACER.records("make_problem")]
    finally:
        trace.TRACER.reset()
        trace.configure(enabled=was)
    assert attrs == [{"n": 9, "p": 12, "G": 3, "ng": 4, "bytes": 9 * 12 * 8},
                     {"n": 9, "p": 12, "G": 3, "ng": 5, "bytes": 9 * 15 * 8}]
    assert len(sets) == 2 and all(s > 0 for s in sets)


def test_make_problem_gauge_is_in_the_registry():
    from repro.obs.metrics import REGISTRY

    X = np.ones((3, 4))
    sgl.make_problem(X, np.ones(3), [2, 2], tau=0.5)
    assert REGISTRY.get("sgl.make_problem_s").value > 0


def test_climate_drop_point_removes_one_group_bit_for_bit():
    kw = dict(n=60, n_lon=6, n_lat=4, seed=3)
    X, y, beta, sizes = make_climate_like(**kw)
    i_lon, i_lat = 4, 1
    g = i_lon * 4 + i_lat
    Xd, yd, betad, sizesd = make_climate_like(**kw, drop_point=(i_lon, i_lat))
    assert sizesd == [7] * (len(sizes) - 1)
    assert Xd.shape == (60, 7 * 23)
    kept = np.delete(np.arange(X.shape[1]), np.arange(7 * g, 7 * g + 7))
    assert Xd.tobytes() == np.ascontiguousarray(X[:, kept]).tobytes()
    # The ground truth lies on the groups that remain, and y is drawn on it.
    assert betad.shape == (Xd.shape[1],) and np.count_nonzero(betad) == 6 * 3
    assert abs(np.mean(yd)) < 1e-12
    np.testing.assert_allclose(yd, yd - np.mean(yd))
    # Without drop_point the generator is what it was.
    X2, y2, beta2, _ = make_climate_like(**kw, drop_point=None)
    assert X2.tobytes() == X.tobytes() and y2.tobytes() == y.tobytes()
    assert beta2.tobytes() == beta.tobytes()


@pytest.mark.parametrize("point", [(6, 0), (0, 4), (-1, 0)])
def test_climate_drop_point_off_the_grid_raises(point):
    with pytest.raises(ValueError, match="off the"):
        make_climate_like(n=24, n_lon=6, n_lat=4, drop_point=point)


def test_climate_published_width_drops_dakar():
    """144 x 73 grid points of 7 variables less the target point's own:
    p = 73,577 in 10,511 groups (at a few samples, not the full design)."""
    X, _, _, sizes = make_climate_like(n=13, n_lon=144, n_lat=73,
                                       drop_point=(137, 30))
    assert len(sizes) == 10_511 and sum(sizes) == 73_577
    assert X.shape == (13, 73_577)
