"""Algorithmic work and peaks, against numbers worked by hand."""
import pytest

from chipbench import peaks, work


def test_apply_work_by_hand():
    # 1000 nonzeros, 64 voxels in, 48 rays out, 8 slices, f16 values and
    # vectors: 2*1000*8 flops; 1000*(2+2) + (64+48)*8*2 bytes
    w = work.apply(1000, 64, 48, 8, value_bytes=2, vector_bytes=2)
    assert w.flops == 16000 and w.bytes == 4000 + 1792


def test_cgnr_work_by_hand():
    nnz, nv, nr, f, it = 1000, 64, 48, 8, 3
    w = work.cgnr(nnz, nv, nr, f, it, value_bytes=4, vector_bytes=4)
    pair_flops = 2 * (2 * nnz * f)
    pair_bytes = 2 * nnz * 6 + 2 * (nv + nr) * f * 4
    vec_flops = 6 * (nv + nr) * f
    vec_bytes = (5 * nv + 3 * nr) * f * 4
    io = (nr + nv) * f * 4
    assert w.flops == (it + 1) * pair_flops + it * vec_flops
    assert w.bytes == (it + 1) * pair_bytes + it * vec_bytes + io


def test_least_time_names_its_bound():
    v5e = peaks.peaks("TPU v5 lite")
    t, bound = work.least_seconds(work.Work(flops=197e12, bytes=1.0), v5e)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(work.Work(flops=1.0, bytes=819e9), v5e)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
