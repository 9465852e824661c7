"""Plain CGNR from x0 = 0 with a fixed iteration count, the recurrence
the paper runs (30 CG iterations, 31 projections and 31
backprojections per slice), on a SciPy matrix split into row blocks so
that a few threads share each product.

``cgnr(op, y, iters)`` runs in float64.  ``high_operator`` gives the
same matrix with every product taken as three bfloat16 passes, the
precision JAX calls ``Precision.HIGH``: the control for a configuration
that states float32 products at ``Precision.HIGHEST``.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

THREADS = 8


class Operator:
    """``A`` and ``A^T`` of one CSR matrix, each applied as row blocks
    in a thread pool (SciPy releases the interpreter lock inside the
    product)."""

    def __init__(self, a, dtype=np.float64, threads: int = THREADS,
                 at=None):
        """``at``: ``a.T`` as CSR, where the caller has it already."""
        self.shape = a.shape
        self.dtype = np.dtype(dtype)
        at = a.T.tocsr() if at is None else at
        self._a = self._blocks(a.astype(self.dtype), threads)
        self._at = self._blocks(at.astype(self.dtype), threads)
        self._pool = ThreadPoolExecutor(threads)

    @staticmethod
    def _blocks(m, k):
        cuts = np.linspace(0, m.shape[0], k + 1).astype(int)
        return [m[c0:c1] for c0, c1 in zip(cuts[:-1], cuts[1:])]

    def _apply(self, blocks, v):
        v = np.ascontiguousarray(v, self.dtype)
        return np.concatenate(list(self._pool.map(lambda b: b @ v, blocks)))

    def matvec(self, x):
        return self._apply(self._a, x)

    def rmatvec(self, r):
        return self._apply(self._at, r)

    def close(self):
        self._pool.shutdown()


def _bf16(v):
    return v.astype(ml_dtypes.bfloat16).astype(np.float32)


class HighOperator:
    """float32 products as three bfloat16 passes: with ``a = a1 + a2``
    and ``x = x1 + x2`` split into bfloat16 parts, ``a x`` is taken as
    ``a1 x1 + a1 x2 + a2 x1``, accumulated in float32."""

    def __init__(self, a, threads: int = THREADS):
        a = a.astype(np.float32)
        hi = a.copy()
        hi.data = _bf16(a.data)
        lo = a.copy()
        lo.data = _bf16(a.data - hi.data)
        self.shape = a.shape
        self.dtype = np.dtype(np.float32)
        self._hi = Operator(hi, np.float32, threads)
        self._lo = Operator(lo, np.float32, threads)

    def _three(self, f_hi, f_lo, v):
        v = np.asarray(v, np.float32)
        v1 = _bf16(v)
        v2 = _bf16(v - v1)
        return f_hi(v1) + f_hi(v2) + f_lo(v1)

    def matvec(self, x):
        return self._three(self._hi.matvec, self._lo.matvec, x)

    def rmatvec(self, r):
        return self._three(self._hi.rmatvec, self._lo.rmatvec, r)

    def close(self):
        self._hi.close()
        self._lo.close()


def cgnr(op, y, iters: int):
    """CGNR on ``[rows, slices]`` data in ``op.dtype``; returns ``(x,
    resnorms [iters, slices])``, the residual norm after each
    iteration (per slice: slices never couple)."""
    dt = op.dtype
    tiny = np.finfo(dt).tiny
    r = np.array(y, dt)
    x = np.zeros((op.shape[1], r.shape[1]), dt)
    s = op.rmatvec(r)
    p = s.copy()
    gamma = (s * s).sum(0)
    res = []
    for _ in range(iters):
        q = op.matvec(p)
        alpha = (gamma / np.maximum((q * q).sum(0), tiny)).astype(dt)
        x += alpha * p
        r -= alpha * q
        s = op.rmatvec(r)
        gamma_new = (s * s).sum(0)
        p = s + (gamma_new / np.maximum(gamma, tiny)).astype(dt) * p
        gamma = gamma_new
        res.append(np.sqrt((r.astype(np.float64) ** 2).sum(0)))
    return x.astype(np.float64), np.asarray(res)
