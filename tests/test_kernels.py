"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs pure-jnp
oracle, assert_allclose (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(7)


# -- flash attention ------------------------------------------------------------
@pytest.mark.parametrize("b,s,hq,hkv,dh", [
    (1, 128, 4, 4, 64), (2, 256, 4, 2, 64), (1, 512, 8, 2, 128),
    (2, 128, 6, 3, 32), (1, 384, 2, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, hq, hkv, dh, dtype):
    from repro.kernels.flash_attention import ops, ref
    q = jnp.asarray(RNG.standard_normal((b, s, hq, dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, dh)), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.gqa_attention(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_non_causal():
    from repro.kernels.flash_attention import ops, ref
    q = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False)
    want = ref.gqa_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_ragged_fallback():
    from repro.kernels.flash_attention import ops, ref
    q = jnp.asarray(RNG.standard_normal((1, 100, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 100, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 100, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)    # oracle fallback
    want = ref.gqa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# -- rwkv6 chunked scan -----------------------------------------------------------
@pytest.mark.parametrize("b,s,h,d,chunk", [
    (1, 64, 1, 16, 16), (2, 128, 2, 32, 32), (1, 256, 4, 64, 64),
    (2, 96, 2, 32, 32),
])
def test_rwkv6_scan_sweep(b, s, h, d, chunk):
    from repro.kernels.rwkv6_scan import ops, ref
    r = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.85, 0.999, (b, s, h, d)), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((h, d)), jnp.float32)
    s0 = jnp.asarray(RNG.standard_normal((b, h, d, d)), jnp.float32) * 0.3
    y, sf = ops.wkv6(r, k, v, w, u, state=s0, chunk=chunk)
    yr, sr = ref.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               atol=5e-4, rtol=5e-4)


def test_rwkv6_zero_state_and_model_consistency():
    """Kernel == model-internal reference scan (models/rwkv6.wkv_scan)."""
    from repro.kernels.rwkv6_scan import ops
    from repro.models.rwkv6 import wkv_scan
    b, s, h, d = 1, 64, 2, 32
    r = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.9, 0.999, (b, s, h, d)), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((h, d)), jnp.float32)
    y1, s1 = ops.wkv6(r, k, v, w, u, chunk=32)
    y2, s2 = wkv_scan(r, k, v, w, u, jnp.zeros((b, h, d, d), jnp.float32))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-4, rtol=5e-4)


# -- mamba selective scan ----------------------------------------------------------
@pytest.mark.parametrize("b,s,di,n,bd,chunk", [
    (1, 64, 64, 8, 64, 32), (2, 128, 128, 16, 64, 64),
    (1, 96, 256, 16, 128, 32),
])
def test_mamba_scan_sweep(b, s, di, n, bd, chunk):
    from repro.kernels.mamba_scan import ops, ref
    u = jnp.asarray(RNG.standard_normal((b, s, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, di)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (di, n)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    c = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((b, di, n)), jnp.float32) * 0.2
    y, h = ops.selective_scan(u, dt, a, bb, c, h0=h0, bd=bd, chunk=chunk)
    yr, hr = ref.selective_scan(u, dt, a, bb, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=5e-5, rtol=5e-5)


def _scan_inputs(b, s, di, n, dtype, h0_scale):
    u = jnp.asarray(RNG.standard_normal((b, s, di)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, di)), dtype)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (di, n)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)), dtype)
    c = jnp.asarray(RNG.standard_normal((b, s, n)), dtype)
    h0 = jnp.asarray(RNG.standard_normal((b, di, n)), jnp.float32) * h0_scale
    return u, dt, a, bb, c, h0


@pytest.mark.parametrize("b,s,di,n,bd,chunk,dtype,h0_scale", [
    (1, 64, 256, 16, 128, 32, jnp.float32, 0.0),      # (N, BD) lanes
    (1, 64, 256, 16, 256, 32, jnp.float32, 0.5),      # h0 seeded in VMEM
    (2, 100, 128, 16, 128, 32, jnp.float32, 0.2),     # ragged S: dt = 0 pad
    (1, 64, 256, 16, 128, 32, jnp.bfloat16, 0.2),     # bf16 u/dt/B/C
    (1, 70, 384, 16, 128, 32, jnp.bfloat16, 0.2),     # ragged, 3 blocks
], ids=["lanes", "h0", "ragged", "bf16", "bf16-ragged"])
def test_mamba_scan_lane_dense(b, s, di, n, bd, chunk, dtype, h0_scale):
    from repro.kernels.mamba_scan import ops, ref
    u, dt, a, bb, c, h0 = _scan_inputs(b, s, di, n, dtype, h0_scale)
    y, h = ops.selective_scan(u, dt, a, bb, c, h0=h0, bd=bd, chunk=chunk)
    yr, hr = ref.selective_scan(u, dt, a, bb, c, h0)
    assert y.shape == yr.shape and y.dtype == jnp.float32
    assert h.shape == hr.shape and h.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=5e-5, rtol=5e-5)


def test_mamba_scan_grad_is_the_reference_grad():
    from repro.kernels.mamba_scan import ops, ref
    u, dt, a, bb, c, h0 = _scan_inputs(1, 40, 128, 16, jnp.float32, 0.3)
    wy = jnp.asarray(RNG.standard_normal((1, 40, 128)), jnp.float32)
    wh = jnp.asarray(RNG.standard_normal((1, 128, 16)), jnp.float32)

    def loss(scan):
        def f(*args):
            y, h = scan(*args)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return f

    kernel = loss(lambda *x: ops.selective_scan(*x, bd=128, chunk=32))
    got = jax.grad(kernel, argnums=range(6))(u, dt, a, bb, c, h0)
    want = jax.grad(loss(ref.selective_scan), argnums=range(6))(
        u, dt, a, bb, c, h0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("platform,s,di,kernel", [
    ("tpu", 2048, 5120, True),       # the icu-jamba2 cell's scan
    ("cpu", 2048, 5120, False),
    ("tpu", 1, 5120, False),         # decode
    ("tpu", 2048, 5000, False),      # no whole 128-lane tiles
])
def test_mamba_scan_path(platform, s, di, kernel):
    from repro.kernels.mamba_scan import ops
    assert ops.uses_kernel(platform, s, di) is kernel


def test_mamba_scan_on_the_cpu_is_the_reference_bitwise():
    from repro.kernels.mamba_scan import ops, ref
    u, dt, a, bb, c, h0 = _scan_inputs(1, 300, 128, 16, jnp.bfloat16, 0.2)
    for got, want in zip(ops.scan(u, dt, a, bb, c, h0),
                         ref.selective_scan(u, dt, a, bb, c, h0)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- quant cast ----------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1000,), (64, 128), (3, 7, 33),
                                   (8, 128)])
def test_quant_roundtrip_error_bound(shape):
    from repro.kernels.quant_cast import ops
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    q, scale = ops.quantize(x)
    back = ops.dequantize(q, scale, shape)
    # per-block bound: |err| <= scale/2 <= absmax/254 * ~1.01
    err = jnp.abs(back - x)
    bound = float(jnp.max(jnp.abs(x))) / 127.0 * 0.51 + 1e-7
    assert float(jnp.max(err)) <= bound * 2.01


def test_quant_kernel_matches_ref():
    from repro.kernels.quant_cast import ops, ref
    from repro.kernels.quant_cast import quant_cast as k
    rng = np.random.default_rng(123)
    x2d = jnp.asarray(rng.standard_normal((32, k.BLOCK)), jnp.float32)
    qk, sk = k.quantize_2d(x2d, interpret=True)
    qr, sr = ref.quantize_blocks(x2d)
    # values exactly at a .5 rounding boundary may differ by 1 LSB between
    # the interpreter and the jnp path; dequantized error stays bounded
    assert int(np.abs(np.asarray(qk, np.int32)
                      - np.asarray(qr, np.int32)).max()) <= 1
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-7)
    back_k = k.dequantize_2d(qk, sk, interpret=True)
    back_r = ref.dequantize_blocks(qr, sr)
    np.testing.assert_allclose(np.asarray(back_k), np.asarray(back_r),
                               atol=float(sr.max()), rtol=1e-6)


def test_quant_zero_block():
    from repro.kernels.quant_cast import ops
    x = jnp.zeros((256,), jnp.float32)
    q, scale = ops.quantize(x)
    back = ops.dequantize(q, scale, (256,))
    assert float(jnp.max(jnp.abs(back))) == 0.0
