"""The benchmark's own reference against the program's, and against
plain dense arithmetic."""
import numpy as np
import pytest

from chipbench.reference import cgnr as ref_cgnr
from chipbench.reference.phantom import phantom
from chipbench.reference.siddon import system_matrix


@pytest.mark.parametrize("n,angles", [(32, 48), (33, 17)])
def test_tracer_equals_the_programs(n, angles):
    from repro.core.geometry import XCTGeometry, build_system_matrix

    ours = system_matrix(n, angles)
    prog = build_system_matrix(XCTGeometry(n=n, n_angles=angles))
    assert ours.shape == prog.shape
    np.testing.assert_array_equal(ours.indptr, prog.indptr)
    np.testing.assert_array_equal(ours.indices, prog.indices)
    # the program stores float32 lengths
    np.testing.assert_allclose(ours.data, prog.data, rtol=1e-7, atol=0)


def test_phantom_is_seeded():
    a, b, c = phantom(32, 8, 1), phantom(32, 8, 1), phantom(32, 8, 2)
    assert a.shape == (32 * 32, 8) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def _dense_cgnr(a, y, iters):
    x = np.zeros((a.shape[1], y.shape[1]))
    r = y.copy()
    s = a.T @ r
    p, gamma, res = s.copy(), (s * s).sum(0), []
    for _ in range(iters):
        q = a @ p
        alpha = gamma / (q * q).sum(0)
        x, r = x + alpha * p, r - alpha * q
        s = a.T @ r
        gamma, old = (s * s).sum(0), gamma
        p = s + gamma / old * p
        res.append(np.linalg.norm(r, axis=0))
    return x, np.array(res)


def test_cgnr_matches_dense_float64():
    a = system_matrix(16, 24)
    y = a @ phantom(16, 3, 0).astype(np.float64)
    op = ref_cgnr.Operator(a, threads=3)
    x, res = ref_cgnr.cgnr(op, y, 6)
    op.close()
    xd, resd = _dense_cgnr(a.toarray(), y, 6)
    np.testing.assert_allclose(x, xd, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(res, resd, rtol=1e-10)


def test_high_products_sit_between_bfloat16_and_float32():
    a = system_matrix(16, 24)
    x = np.random.default_rng(0).random((256, 4)).astype(np.float32)
    exact = a @ x.astype(np.float64)
    high = ref_cgnr.HighOperator(a, threads=2)
    err = np.abs(high.matvec(x) - exact).max() / np.abs(exact).max()
    high.close()
    assert 1e-7 < err < 2.0 ** -12
