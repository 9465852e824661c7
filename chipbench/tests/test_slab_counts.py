"""The stream driver counts, prices and samples each slab by the slices
it holds, so a scan whose slice count is not a multiple of the slab (a
short last slab) is measured as it is; whole slabs count as before."""
import types

import numpy as np
import pytest
from conftest import ROOT

from chipbench import harness, work
from chipbench.drivers import stream

SHALE = harness.load_json(ROOT / "chipbench" / "configs" / "shale-b8.json")
BRAIN = harness.load_json(ROOT / "chipbench" / "configs" / "brain-b32.json")
TRAFFIC = harness.load_json(ROOT / "chipbench" / "traffic" / "recon30.json")
NNZ = 14_700_000


def _ctx(config, seed=1, **cfg):
    return stream.Context(
        cell="test", config=dict(config, **cfg), traffic=TRAFFIC, limits={},
        seed=seed, seconds=0.0, trace=False, root=ROOT, t_start=0.0,
    )


def _scan(solved=(), escalated=(), failed_slabs=(), retries=0):
    return ("vol", types.SimpleNamespace(
        solved=list(solved), escalated=list(escalated),
        failed_slabs=list(failed_slabs), retries=retries))


def _pass(ctx, fused):
    """One pass of ``fused`` slices: its applies and its solve."""
    from repro.core.precision import get_policy

    cfg, it = ctx.config, ctx.traffic["iters"]
    pol = get_policy(cfg["rung"])
    n_vox, n_rays = cfg["channels"] ** 2, cfg["channels"] * cfg["angles"]
    args = dict(slices=fused, value_bytes=pol.vals_bytes,
                vector_bytes=pol.storage_bytes)
    applies = (it + 1) * (work.apply(NNZ, n_vox, n_rays, **args)
                          + work.apply(NNZ, n_rays, n_vox, **args))
    return applies, work.cgnr(NNZ, n_vox, n_rays, iters=it, **args)


def _close(got: work.Work, want: work.Work, rel=1e-12):
    assert got.flops == pytest.approx(want.flops, rel=rel)
    assert got.bytes == pytest.approx(want.bytes, rel=rel)


@pytest.mark.parametrize("config", [SHALE, BRAIN], ids=["shale", "brain"])
def test_whole_slabs_count_as_before(config):
    ctx = _ctx(config)
    slab, n = TRAFFIC["slab"], config["slices"]
    scans = [_scan([0, 128]), _scan([0, 128], escalated=[128]),
             _scan([0], failed_slabs=[128], retries=2)]
    attempted, slices, failed = stream.counts(ctx, scans)
    solved = sum(len(r.solved) for _, r in scans)
    assert attempted == len(scans) * n == 768
    assert slices == solved * slab == 640
    assert failed == slab * (1 + 1 + 2) == 512
    # the work of a slab as it was priced before: slab // fuse passes
    got = stream.work_per_slab(ctx, types.SimpleNamespace(nnz=NNZ),
                               config["rung"], [0, 128] * 3)
    applies, solve = _pass(ctx, config["fuse"])
    batches = slab // config["fuse"]
    _close(got["applies"], batches * applies)
    _close(got["solve"], batches * solve)


def test_a_short_last_slab_counts_its_own_slices():
    ctx = _ctx(SHALE, slices=262)
    scans = [_scan([0, 128, 256]), _scan([0, 128, 256])]
    attempted, slices, failed = stream.counts(ctx, scans)
    assert (attempted, slices, failed) == (524, 524, 0)
    starts = [j0 for _, r in scans for j0 in r.solved]
    got = stream.work_per_slab(ctx, types.SimpleNamespace(nnz=NNZ),
                               "mixed", starts)
    full_a, full_s = _pass(ctx, 128)
    short_a, short_s = _pass(ctx, 6)
    # two full passes and one of 6 fused slices a scan, over 3 slabs
    _close(got["applies"], (2 * full_a + short_a) * (1 / 3))
    _close(got["solve"], (2 * full_s + short_s) * (1 / 3))
    assert short_a.bytes < full_a.bytes and short_a.flops < full_a.flops


@pytest.mark.parametrize("where", ["failed_slabs", "escalated"])
def test_a_failed_short_slab_charges_its_own_slices(where):
    ctx = _ctx(SHALE, slices=262)
    attempted, slices, failed = stream.counts(
        ctx, [_scan([0, 128], **{where: [256]})])
    assert (attempted, slices, failed) == (262, 256, 6)
    # a retry names no slab: a whole one, capped at what was attempted
    assert stream.counts(ctx, [_scan([0, 128, 256], retries=1)])[2] == 128
    assert stream.counts(ctx, [_scan(retries=3)])[2] == 262


@pytest.mark.parametrize("config,seed,want", [
    (SHALE, 1, [87, 89, 129, 140, 155, 163, 181, 193]),
    (SHALE, 2147483923, [111, 124, 129, 141, 171, 200, 217, 220]),
    (BRAIN, 7, [16, 29, 42, 75, 78, 101, 114, 147]),
    (BRAIN, 2147483703, [29, 30, 54, 130, 139, 141, 143, 192]),
], ids=["shale-1", "shale-2147483923", "brain-7", "brain-2147483703"])
def test_sample_of_whole_slabs_is_unchanged(config, seed, want):
    assert stream.sample(_ctx(config, seed)).tolist() == want


def test_sample_always_holds_a_slice_of_the_short_slab():
    for seed in list(range(200)) + [2**31 + 5, 2**32 + 17]:
        idx = stream.sample(_ctx(SHALE, seed, slices=262))
        drawn = np.sort(np.random.default_rng([seed, 7]).choice(
            262, stream.SAMPLE, replace=False))
        assert np.all(np.diff(idx) > 0) and len(idx) == stream.SAMPLE
        assert ((idx >= 256) & (idx < 262)).any()
        # the seeded draw, but for its largest slice where none was short
        keep = len(idx) if drawn[-1] >= 256 else -1
        assert idx[:keep].tolist() == drawn[:keep].tolist()


def test_slab_width():
    assert [stream.width(j0, 128, 262) for j0 in (0, 128, 256)] == [
        128, 128, 6]
    assert [stream.width(j0, 128, 256) for j0 in (0, 128)] == [128, 128]
