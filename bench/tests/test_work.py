"""bench/work.py against a count by hand at one shape."""
import itertools

import work

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_band_pairs_match_enumeration():
    for n, k in ((8, 2), (16, 5), (7, 6), (5, 1)):
        brute = sum(abs(i - j) <= k
                    for i, j in itertools.product(range(n), repeat=2))
        assert work.pairs(n, k) == brute
    assert work.pairs(8, None) == 64 and work.pairs(8, 7) == 64


def test_hand_count_n8_k2_d3():
    # 34 scored pairs; per pair 9 + 2d forward and 11 + 4d backward.
    assert work.apply_flops(1, 8, 2, 3, grad=False) == 34 * (9 + 6)
    assert work.apply_flops(1, 8, 2, 3) == 34 * (9 + 6 + 11 + 12)
    assert work.apply_flops(4, 8, 2, 3) == 4 * 1292
    # forward: w 32 + x 96 + y 96 + c 32; backward: w 32 + x 96 + dy 96
    # + dc 32 + dw 32 + dx 96.
    assert work.apply_bytes(1, 8, 3, grad=False) == 256
    assert work.apply_bytes(1, 8, 3) == 640
    # bfloat16 halves the three payload-sided reads.
    assert work.apply_bytes(1, 8, 3, "bfloat16") == 640 - 3 * 48


def test_roofline_bound():
    t, bound = work.roofline_seconds(197e12, 1.0, PEAK)
    assert bound == "flops" and abs(t - 1.0) < 1e-12
    t, bound = work.roofline_seconds(1.0, 819e9, PEAK)
    assert bound == "bytes" and abs(t - 1.0) < 1e-12
    # A narrow band reads as much as it computes (bytes bound); a wide
    # band at large N computes far more than it moves.
    for n, k, d, want in ((4096, 256, 50, "bytes"), (1024, 64, 50, "bytes"),
                          (65536, 4096, 59, "flops")):
        _, bound = work.roofline_seconds(work.apply_flops(1, n, k, d),
                                         work.apply_bytes(1, n, d), PEAK)
        assert bound == want, (n, k, d)
