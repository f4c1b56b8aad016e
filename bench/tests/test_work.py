import pytest

import work


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_byte_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_counts_from_shapes():
    # deep1b-shard: R=32, m=5, 204-wide member lists -> 32640 ids per query
    ops, nbytes = work.freq_topc(512, 32 * 5 * 204, 1024)
    assert ops == 0 and nbytes == 512 * (32640 * 4 + 1024 * 8)
    ops, nbytes = work.quant_rerank(512, 1024, 96, 32, 40)
    assert ops == 2 * 512 * 1024 * 96
    assert nbytes == 512 * (96 * 4 + 1024 * (96 + 3 * 4 + 8) + 40 * 8)
    assert work.scorer_flops(32, 96, 1024, 20000) == pytest.approx(1.317e9,
                                                                  rel=1e-3)
    assert work.serve_flops(32, 96, 1024, 20000, 1024, 40) == (
        work.scorer_flops(32, 96, 1024, 20000) + 2 * 96 * 1064)


def test_roofline_share():
    p = work.peaks("TPU v5 lite")
    # 819 MB in 2 ms is half the bandwidth roofline
    assert work.roofline_share(0, 819e6, 2e-3, p) == pytest.approx(50.0)
    # compute-bound: 197 GFLOP in 1 ms is the whole peak
    assert work.roofline_share(197e9, 1.0, 1e-3, p) == pytest.approx(100.0)
    assert work.roofline_share(1.0, 1.0, 0.0, p) is None


def test_shard_fits_the_kernel_width():
    """The shard's cut: member width 2*floor(L/B), and the candidate width
    m*R*member_width, padded to a power of two, within freq_topc's lanes;
    one more bucket load's worth of rows would not be."""
    import harness as H
    from repro.kernels.freq_topc.freq_topc import MAX_WIDTH, pow2_width
    cf = H.cell(H.spec(), "shard-saturated")[1]
    L, B = cf["n_corpus"], cf["buckets"]
    assert cf["member_width"] == 2 * (L // B)
    assert L % cf["affinity_chunk"] == 0 and L % cf["cluster_rows"] == 0
    assert pow2_width(cf["m"] * cf["reps"] * cf["member_width"]) <= MAX_WIDTH
    assert cf["m"] * cf["reps"] * 2 * (L // B + 1) > MAX_WIDTH
