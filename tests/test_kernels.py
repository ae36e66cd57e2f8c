"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Shape/dtype sweeps for the single-device kernels; the remote-DMA kernels
are swept in tests/multidev_kernels_driver.py (8 simulated devices).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunked_gemm import accumulate_matmul, chunked_matmul

SHAPES = [
    (128, 128, 128),
    (256, 128, 384),
    (384, 256, 128),
    (128, 384, 256),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return (
        dict(rtol=2e-2, atol=2e-2)
        if dtype == jnp.bfloat16
        # fp32 dots reassociate across K blocks -> not bit-equal to jnp
        else dict(rtol=1e-4, atol=1e-4)
    )


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_matmul_matches_ref(m, n, k, dtype):
    rng = np.random.default_rng(m + n + k)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)), dtype)
    got = chunked_matmul(x, w, interpret=True)
    want = ref.matmul_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        **_tol(dtype),
    )


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_accumulate_matmul_matches_ref(m, n, k, dtype):
    rng = np.random.default_rng(7 * m + n + k)
    c = jnp.asarray(rng.standard_normal((m, n)), dtype)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)), dtype)
    got = accumulate_matmul(c, x, w, interpret=True)
    want = ref.accumulate_matmul_ref(c, x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_block_shape_sweep():
    """BlockSpec tiling must not change results."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    want = np.asarray(ref.matmul_ref(x, w))
    for bm, bn, bk in [(128, 128, 128), (256, 128, 128), (128, 256, 256)]:
        got = chunked_matmul(
            x, w, block_m=bm, block_n=bn, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-4, atol=1e-4,
            err_msg=f"blocks {bm},{bn},{bk}",
        )


def test_indivisible_raises():
    x = jnp.zeros((100, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    with pytest.raises(ValueError):
        chunked_matmul(x, w, interpret=True)


def test_accumulate_fallback_for_odd_shapes():
    """accumulate_matmul degrades to jnp for non-tileable shapes."""
    rng = np.random.default_rng(4)
    c = jnp.asarray(rng.standard_normal((100, 60)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((100, 30)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((30, 60)), jnp.float32)
    got = accumulate_matmul(c, x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.accumulate_matmul_ref(c, x, w)),
        rtol=1e-5, atol=1e-5,
    )


def test_ops_refuse_backends_without_pallas_tpu(monkeypatch):
    """Only a TPU compiles the kernels and only the CPU interprets them;
    any other backend is refused instead of silently interpreted."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    x = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="gpu"):
        ops.matmul(x, x)


def test_ops_wrappers_interpret_on_cpu():
    assert jax.default_backend() == "cpu"
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    got = ops.matmul(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.matmul_ref(x, w)),
        rtol=1e-5, atol=1e-5,
    )
