"""N-D plan-graph engine: parity, pass counts, kernel routing, serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional test dep: skip property tests
    from _hyp import given, settings, st

from repro.fft import fft2, fftn, plan_nd, rfft2, rfftn
from repro.fft import plan as plan_mod
from repro.fft.plan_nd import nd_pass_summary

KEY = jax.random.PRNGKey(0)


def rand_complex(shape, key=KEY, dtype=jnp.complex64):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(dtype)


def assert_close(got, want, rtol=3e-3, atol=3e-3):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Parity vs jnp.fft across length classes (pow2 / four-step / Bluestein)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (8, 16), (32, 32), (64, 128),          # pow2: fully fused, 2 passes
    (4, 2**14),                            # four-step axis in a 2-D plan
    (12, 32), (16, 100), (45, 39),         # Bluestein axes (one or both)
])
def test_fft2_matches_reference(shape):
    x = rand_complex((3, *shape))
    assert_close(fft2(x), jnp.fft.fft2(x))


@pytest.mark.parametrize("shape", [
    (8, 16), (32, 32), (16, 2**14), (12, 32), (16, 100),
])
def test_rfft2_matches_reference(shape):
    x = jax.random.normal(KEY, (2, *shape))
    assert_close(rfft2(x), jnp.fft.rfft2(x))


@pytest.mark.parametrize("shape", [(4, 8, 16), (8, 8, 8), (4, 12, 16)])
def test_fftn_matches_reference(shape):
    x = rand_complex((2, *shape))
    assert_close(fftn(x, axes=(1, 2, 3)), jnp.fft.fftn(x, axes=(1, 2, 3)))


@pytest.mark.parametrize("shape", [(4, 8, 16), (4, 12, 16)])
def test_rfftn_matches_reference(shape):
    x = jax.random.normal(KEY, (2, *shape))
    assert_close(rfftn(x, axes=(1, 2, 3)), jnp.fft.rfftn(x, axes=(1, 2, 3)))


def test_fftn_default_axes_and_moveaxis_normalisation():
    x = rand_complex((8, 4, 16))
    assert_close(fftn(x), jnp.fft.fftn(x))
    assert_close(fft2(x, axes=(0, 2)), jnp.fft.fft2(x, axes=(0, 2)))


def test_four_step_parity_tight():
    """Acceptance: fused four-step matches jnp.fft.fft at 1e-4 rtol."""
    n = 2**14
    x = rand_complex((2, n), key=jax.random.PRNGKey(5))
    got = np.asarray(plan_mod.plan_for_length(n)(x))
    want = np.fft.fft(np.asarray(x), axis=-1)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 1e-4, rel


@settings(deadline=None, max_examples=15)
@given(log0=st.integers(1, 6), log1=st.integers(2, 6),
       seed=st.integers(0, 2**31 - 1))
def test_property_fft2_pow2_parity(log0, log1, seed):
    x = rand_complex((2, 2**log0, 2**log1), key=jax.random.PRNGKey(seed))
    assert_close(fft2(x), jnp.fft.fft2(x))


@settings(deadline=None, max_examples=15)
@given(log0=st.integers(1, 5), log1=st.integers(2, 6),
       seed=st.integers(0, 2**31 - 1))
def test_property_rfft2_pow2_parity(log0, log1, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 2**log0, 2**log1))
    assert_close(rfft2(x), jnp.fft.rfft2(x))


@settings(deadline=None, max_examples=10)
@given(n0=st.sampled_from([3, 12, 20, 45]), log1=st.integers(3, 6),
       seed=st.integers(0, 2**31 - 1))
def test_property_fft2_bluestein_axis_parity(n0, log1, seed):
    """One Bluestein axis + one pow2 axis — the mixed plan graph."""
    x = rand_complex((2, n0, 2**log1), key=jax.random.PRNGKey(seed))
    assert_close(fft2(x), jnp.fft.fft2(x))


@settings(deadline=None, max_examples=8)
@given(logn=st.integers(1, 4), seed=st.integers(0, 2**31 - 1))
def test_property_fftn_3d_parity(logn, seed):
    n = 2**logn
    x = rand_complex((n, n, n), key=jax.random.PRNGKey(seed))
    assert_close(fftn(x), jnp.fft.fftn(x))


# ---------------------------------------------------------------------------
# Plan-graph structure and pass accounting
# ---------------------------------------------------------------------------

def test_pow2_2d_plan_is_two_fused_passes():
    plan = plan_nd((256, 512))
    assert [n.op for n in plan.nodes] == ["fft_t", "fft_t"]
    assert plan.passes == 2
    # the per-axis moveaxis chain paid 1 (last axis) + 1 + 2 (moveaxis
    # there and back) = 4 -> the acceptance >= 2x pass reduction
    assert plan.chain_passes >= 2 * plan.passes


def test_pow2_r2c_2d_plan_structure():
    plan = plan_nd((256, 512), "r2c")
    assert [n.op for n in plan.nodes] == ["rfft_t", "fft_t"]
    assert plan.passes == 2
    assert plan.out_shape == (256, 257)


def test_pow2_3d_plan_is_three_fused_passes():
    plan = plan_nd((16, 16, 16))
    assert [n.op for n in plan.nodes] == ["fft_t"] * 3
    assert plan.passes == 3
    assert plan.chain_passes == 1 + 3 + 3


def test_bluestein_axis_gets_explicit_transpose_node():
    plan = plan_nd((12, 32))
    ops = [n.op for n in plan.nodes]
    assert ops == ["fft_t", "fft1d", "transpose"]
    assert plan.nodes[1].algorithm == "bluestein"


def test_plan_nd_1d_delegates_to_planner():
    plan = plan_nd((4096,))
    ref = plan_mod.plan_for_length(4096)
    assert plan.passes == ref.passes
    assert plan.algorithm == ref.algorithm
    x = rand_complex((2, 4096))
    assert_close(plan(x), jnp.fft.fft(x))


def test_nd_pass_summary_matches_plan():
    passes, chain, stages = nd_pass_summary((64, 64))
    plan = plan_nd((64, 64))
    assert (passes, chain, stages) == (plan.passes, plan.chain_passes,
                                       plan.stages)


def test_plan_nd_rejects_bad_specs():
    with pytest.raises(ValueError):
        plan_nd((0, 8))
    with pytest.raises(ValueError):
        plan_nd((8, 8), "hartley")


# ---------------------------------------------------------------------------
# Kernel routing: the 2-D path launches exactly its plan's fused passes
# ---------------------------------------------------------------------------

class _CountingKernel:
    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.inner(*args, **kwargs)


def test_fft2_launches_exactly_two_fused_passes(monkeypatch):
    """Acceptance: no hidden fallback to the per-axis chain — the pow2
    2-D path is exactly two fused transpose-write kernel launches."""
    fused = _CountingKernel(plan_mod.fft_kernel_c2c_t)
    plain = _CountingKernel(plan_mod.fft_kernel_c2c)
    tr = _CountingKernel(plan_mod.transpose_kernel)
    monkeypatch.setattr(plan_mod, "_kernel_fft_t", fused)
    monkeypatch.setattr(plan_mod, "_kernel_fft", plain)
    monkeypatch.setattr(plan_mod, "_kernel_transpose", tr)
    x = rand_complex((5, 16, 64))
    assert_close(fft2(x), jnp.fft.fft2(x))
    assert fused.calls == 2
    assert plain.calls == 0
    assert tr.calls == 0


def test_rfft2_launches_fused_r2c_then_c2c(monkeypatch):
    fused_r = _CountingKernel(plan_mod.fft_kernel_r2c_t)
    fused_c = _CountingKernel(plan_mod.fft_kernel_c2c_t)
    monkeypatch.setattr(plan_mod, "_kernel_rfft_t", fused_r)
    monkeypatch.setattr(plan_mod, "_kernel_fft_t", fused_c)
    x = jax.random.normal(KEY, (5, 16, 64))
    assert_close(rfft2(x), jnp.fft.rfft2(x))
    assert fused_r.calls == 1
    assert fused_c.calls == 1


def test_bluestein_axis_routes_tiled_transpose(monkeypatch):
    tr = _CountingKernel(plan_mod.transpose_kernel)
    monkeypatch.setattr(plan_mod, "_kernel_transpose", tr)
    x = rand_complex((4, 12, 32))
    assert_close(fft2(x), jnp.fft.fft2(x))
    assert tr.calls == 1


def test_nd_falls_back_without_pallas(monkeypatch):
    for hook in ("_kernel_fft", "_kernel_rfft", "_kernel_irfft",
                 "_kernel_fft_t", "_kernel_fft_axis1", "_kernel_rfft_t",
                 "_kernel_transpose"):
        monkeypatch.setattr(plan_mod, hook, _no_kernel)
    x = rand_complex((6, 16, 32))
    xr = jax.random.normal(KEY, (6, 16, 32))
    with plan_mod.pallas_disabled():
        assert_close(fft2(x), jnp.fft.fft2(x))
        assert_close(rfft2(xr), jnp.fft.rfft2(xr))


def _no_kernel(*args, **kwargs):
    raise AssertionError("a Pallas kernel ran with Pallas disabled")


# ---------------------------------------------------------------------------
# Cost model threading
# ---------------------------------------------------------------------------

def test_nd_workload_pass_reduction():
    from repro.core.hardware import TESLA_V100
    from repro.core.workloads import FFTCase, fft_workload
    case = FFTCase(shape=(1024, 1024))
    prof = fft_workload(case, TESLA_V100)
    assert prof.t_mem > 0 and prof.flops > 0
    passes, chain, _ = nd_pass_summary((1024, 1024))
    assert passes == 2 and chain == 4
    # the modelled memory time scales with the plan's pass count
    single = fft_workload(FFTCase(n=1024, batch_bytes=case.batch_bytes),
                          TESLA_V100)
    assert prof.t_mem == pytest.approx(2 * single.t_mem, rel=0.02)


def test_nd_workload_r2c_cheaper_per_transform():
    from repro.core.hardware import TESLA_V100
    from repro.core.workloads import FFTCase, fft_workload
    c = FFTCase(shape=(512, 512))
    r = FFTCase(shape=(512, 512), transform="r2c")
    pc = fft_workload(c, TESLA_V100)
    pr = fft_workload(r, TESLA_V100)
    assert pr.t_mem / r.n_fft < 0.6 * (pc.t_mem / c.n_fft)
    assert pr.flops / r.n_fft < 0.6 * (pc.flops / c.n_fft)


def test_absolute_profile_pass_accounting():
    from repro.core.hardware import TESLA_V100
    from repro.core.perf_model import absolute_profile
    two = absolute_profile("two", device=TESLA_V100, hbm_bytes=0.0,
                           flops=1e9, passes=2, pass_bytes=1e9)
    four = absolute_profile("four", device=TESLA_V100, hbm_bytes=0.0,
                            flops=1e9, passes=4, pass_bytes=1e9)
    assert four.t_mem == pytest.approx(2 * two.t_mem)


# ---------------------------------------------------------------------------
# Serving: 2-D shapes are first-class cacheable plans
# ---------------------------------------------------------------------------

def test_service_serves_2d_shapes_with_cached_plans():
    from repro.serving.service import FFTService
    svc = FFTService(batch_bytes=2**24, time_budget=None)
    x = rand_complex((3, 16, 32), key=jax.random.PRNGKey(7))
    xr = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32))
    r_c2c = svc.submit(x, ndim=2)
    r_r2c = svc.submit(xr, ndim=2, transform="r2c")
    svc.drain()
    assert_close(svc.receipt(r_c2c).result, jnp.fft.fft2(x))
    assert_close(svc.receipt(r_r2c).result, jnp.fft.rfft2(xr))
    assert svc.cache.stats.misses == 2
    # same 2-D shape again: plan + sweep come from the cache
    r2 = svc.submit(x, ndim=2)
    svc.drain()
    assert svc.cache.stats.hits >= 1
    assert svc.receipt(r2).energy_j > 0


def test_2d_and_1d_same_total_points_are_distinct_cache_keys():
    from repro.serving.request import FFTRequest
    a = FFTRequest(x=jnp.zeros((2, 16, 32), jnp.complex64), ndim=2)
    b = FFTRequest(x=jnp.zeros((2, 512), jnp.complex64))
    assert a.n == b.n == 512
    assert a.shape_key("d") != b.shape_key("d")


def test_request_rejects_bad_rank():
    from repro.serving.request import FFTRequest
    with pytest.raises(ValueError):
        FFTRequest(x=jnp.zeros((4, 4), jnp.complex64), ndim=3)
    with pytest.raises(ValueError):
        FFTRequest(x=jnp.zeros((2, 4, 4), jnp.complex64), ndim=2,
                   kind="fdas")
