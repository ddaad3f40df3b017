"""The trip-count-aware HLO cost analyzer (launch/roofline.py).

XLA's own cost_analysis counts while bodies once; these tests pin the
corrected semantics on controlled graphs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from repro.launch.roofline import (
    PEAKS, Roofline, achieved_vs_peak, analyze_hlo, parse_collective_bytes,
)

D = 128
WANT = 2 * D ** 3  # flops of one DxD @ DxD matmul


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def mats():
    W = jnp.zeros((D, D), jnp.float32)
    x = jnp.zeros((D, D), jnp.float32)
    return W, x


def test_single_dot_flops(mats):
    W, x = mats
    a = analyze_hlo(_compile(lambda x: x @ W, x))
    assert a["flops"] == pytest.approx(WANT, rel=0.01)


def test_scan_multiplies_by_trip_count(mats):
    W, x = mats

    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=12)
        return y

    a = analyze_hlo(_compile(f, x))
    assert a["flops"] == pytest.approx(12 * WANT, rel=0.01)


def test_nested_scan(mats):
    W, x = mats

    def f(x):
        def inner(c, _):
            y, _ = jax.lax.scan(lambda d, _: (d @ W, None), c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(inner, x, None, length=3)
        return y

    a = analyze_hlo(_compile(f, x))
    assert a["flops"] == pytest.approx(15 * WANT, rel=0.01)


def test_scan_bytes_scale_with_trips(mats):
    W, x = mats

    def fk(k):
        def f(x):
            y, _ = jax.lax.scan(
                lambda c, _: (c @ W, None), x, None, length=k)
            return y
        return f

    b4 = analyze_hlo(_compile(fk(4), x))["bytes_accessed"]
    b16 = analyze_hlo(_compile(fk(16), x))["bytes_accessed"]
    # bytes should grow ~linearly in trip count (some fixed overhead ok)
    assert 2.5 < b16 / b4 < 4.5


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=197e12 * 256, bytes_accessed=819e9,
                 collective_bytes=0.0, chips=256, model_flops=197e12 * 128)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0 / 256)
    assert r.bottleneck == "compute"
    assert r.roofline_fraction == pytest.approx(0.5)


def test_parse_collective_bytes_counts_result_shapes():
    hlo = """
ENTRY %main (x: f32[16]) -> f32[16] {
  %x = f32[16]{0} parameter(0)
  %ag = f32[64]{0} all-gather(%x), replica_groups={}
  ROOT %ar = f32[16]{0} all-reduce(%x), to_apply=%add
}
"""
    c = parse_collective_bytes(hlo)
    assert c["all-gather"] == 64 * 4
    assert c["all-reduce"] == 16 * 4


def test_achieved_vs_peak_on_cpu_is_not_measured():
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    a = achieved_vs_peak(2e9, 1e9, 0.5, device=cpu)
    assert a["achieved_flops_per_s"] == pytest.approx(4e9)
    for key in ("frac_peak_compute", "frac_peak_memory",
                "achieved_vs_model", "model_bottleneck"):
        assert a[key] is None


def test_achieved_vs_peak_uses_the_device_kind_peak():
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    peak = PEAKS["TPU v5 lite"]
    a = achieved_vs_peak(peak.flops, peak.hbm_bw / 2, 1.0, device=v5e)
    assert a["frac_peak_compute"] == pytest.approx(1.0)
    assert a["frac_peak_memory"] == pytest.approx(0.5)
    assert a["model_bottleneck"] == "compute"
    assert a["achieved_vs_model"] == pytest.approx(1.0)


def test_achieved_vs_peak_unknown_tpu_kind_raises():
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        achieved_vs_peak(1e9, 1e9, 1.0, device=unknown)
