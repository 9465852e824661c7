"""Faults planted in the program's timed path, which ``correct`` has to
fail: in the CPU tests, and on the chip through ``control.py --faults``.

A one-chip reconstruction cell can have these:

``state_unchanged``  every CG step returns its state unchanged (the
                     solve hands back ``x0`` and its residual);
``frozen``           the CG state stops moving after ``check.EARLY``
                     iterations: the solve runs only those and repeats
                     its last residual for the rest, so the early
                     residuals are right and the volume agrees with the
                     residual it reports;
``half_left_out``    half of a slab's slices left out, their answers the
                     mean of the rest;
``answer_altered``   every answer scaled by 1.01 where it is produced.

It has no exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from chipbench import check

NAMES = ("state_unchanged", "frozen", "half_left_out", "answer_altered")


def _stuck_cgnr(apply_a, apply_at, y, x0, iters, dot_rows, **_):
    r = y.astype(jnp.float32) - apply_a(x0).astype(jnp.float32)
    res = jnp.sqrt(dot_rows(r, r))
    return x0, jnp.broadcast_to(res, (iters,) + res.shape)


def _frozen_cgnr(real):
    def cgnr(apply_a, apply_at, y, x0, iters, dot_rows, **kw):
        x, res = real(apply_a, apply_at, y, x0, min(check.EARLY, iters),
                      dot_rows, **kw)
        pad = jnp.broadcast_to(res[-1], (iters - res.shape[0],)
                               + res.shape[1:])
        return x, jnp.concatenate([res, pad])
    return cgnr


def _half_left_out(solve):
    def wrapped(self, sino, iters=30, x0_nat=None):
        x, res = solve(self, sino, iters=iters, x0_nat=x0_nat)
        h = x.shape[1] // 2
        x[:, h:] = x[:, :h].mean(1, keepdims=True)
        res[:, h:] = res[:, :h].mean(1, keepdims=True)
        return x, res
    return wrapped


def _altered(solve):
    def wrapped(self, sino, iters=30, x0_nat=None):
        x, res = solve(self, sino, iters=iters, x0_nat=x0_nat)
        return x * np.float32(1.01), res
    return wrapped


@contextlib.contextmanager
def plant(name: str):
    """The program with fault ``name`` planted, for a ``Reconstructor``
    built inside the block (each traces its solve on first use)."""
    from repro.core import recon, solver

    if name == "state_unchanged":
        obj, attr, new = recon, "cgnr", _stuck_cgnr
    elif name == "frozen":
        obj, attr, new = recon, "cgnr", _frozen_cgnr(solver.cgnr)
    elif name in ("half_left_out", "answer_altered"):
        wrap = _half_left_out if name == "half_left_out" else _altered
        obj, attr = recon.Reconstructor, "reconstruct"
        new = wrap(recon.Reconstructor.reconstruct)
    else:
        raise ValueError(f"unknown fault {name!r}; one of {NAMES}")
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    try:
        yield
    finally:
        setattr(obj, attr, old)
