"""Per-kernel allclose vs pure-jnp oracles, with hypothesis shape/value
sweeps (interpret mode executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the 'test' extra (pip install .[test])")
from hypothesis import given, settings, strategies as st

from repro.kernels.crossbar_vmm import ops as xb_ops
from repro.kernels.crossbar_vmm import ref as xb_ref
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan import ref as ssm_ref


@settings(max_examples=12, deadline=None)
@given(
    r=st.integers(1, 300),
    c=st.integers(1, 300),
    in_res=st.sampled_from([2, 4, 8]),
    out_res=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_crossbar_kernel_matches_ref(r, c, in_res, out_res, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-128, 128, (r, c)), jnp.int8)
    x = jnp.asarray(rng.integers(-(1 << 12), 1 << 12, (c,)), jnp.int32)  # exercises DAC clamp
    ref = xb_ref.crossbar_vmm(w, x, in_res, out_res)
    ker = xb_ops.crossbar_vmm(w, x, in_res, out_res)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_crossbar_equals_exact_int_math():
    rng = np.random.default_rng(0)
    w = rng.integers(-128, 128, (256, 256)).astype(np.int8)
    x = rng.integers(-100, 100, (256,)).astype(np.int32)
    got = np.asarray(xb_ref.crossbar_vmm(jnp.asarray(w), jnp.asarray(x), 8, 8))
    exact = np.clip(w.astype(np.int64) @ np.clip(x, -128, 127), -(1 << 15), (1 << 15) - 1)
    np.testing.assert_array_equal(got, exact)


def test_crossbar_adc_saturates():
    w = jnp.full((4, 256), 127, jnp.int8)
    x = jnp.full((256,), 127, jnp.int32)
    out = np.asarray(xb_ref.crossbar_vmm(w, x, 8, 8))
    assert (out == (1 << 15) - 1).all()  # 127*127*256 ≫ ADC full scale


def test_crossbar_matmul_tiled():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.integers(-16, 16, (100, 70)), jnp.int8)
    x = jnp.asarray(rng.integers(-50, 50, (70, 9)), jnp.int32)
    ref = xb_ref.crossbar_matmul(w, x)
    ker = xb_ops.crossbar_matmul(w, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@settings(max_examples=6, deadline=None)
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([64, 128, 192]),
    d=st.sampled_from([128, 256]),
    n=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_ssm_scan_kernel_matches_ref(b, s, d, n, seed):
    rng = np.random.default_rng(seed)
    da = jnp.asarray(rng.uniform(0.5, 0.999, (b, s, d, n)), jnp.float32)
    dbx = jnp.asarray(rng.normal(0, 0.2, (b, s, d, n)), jnp.float32)
    c = jnp.asarray(rng.normal(0, 1.0, (b, s, n)), jnp.float32)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y_ref, _ = ssm_ref.selective_scan(da, dbx, c, h0)
    y_ker = ssm_ops.ssm_scan(da, dbx, c)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ker), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend, interpret", [("cpu", True), ("tpu", False)])
def test_kernel_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """The ops wrappers interpret the kernel bodies on the CPU backend only;
    on a TPU they compile them (the backend is read at trace time)."""
    import jax

    from repro.kernels.lif_step import ops as lif_ops

    seen = {}

    def record(name):
        def kernel(*args, interpret, **kw):
            seen[name] = interpret
            return np.zeros((1, 1))
        return kernel

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(xb_ops, "crossbar_vmm_tiles", record("crossbar_vmm"))
    monkeypatch.setattr(lif_ops, "lif_step_tiles", record("lif_step"))
    monkeypatch.setattr(ssm_ops, "_ssm_scan", record("ssm_scan"))
    xb_ops.crossbar_vmm(None, np.zeros(1))
    lif_ops.lif_step_units(*[None] * 7)
    ssm_ops.ssm_scan(None, None, None)
    assert seen == {"crossbar_vmm": interpret, "lif_step": interpret,
                    "ssm_scan": interpret}
