"""Each kernel's work function against counts made by hand at one shape."""
from bench import catalog


def test_da_vmm_hand_count():
    da = catalog.work("da_vmm")
    ops, nbytes = da.work(32, 4096, 6144)
    assert ops == 2 * 32 * 4096 * 6144  # 1,610,612,736
    # int8 codes 25,165,824 + f32 scales 24,576 + bf16 x 262,144
    # + bf16 y 393,216
    assert nbytes == 25_165_824 + 24_576 + 262_144 + 393_216


def test_paged_attention_hand_count():
    pa = catalog.work("paged_attn")
    shape = dict(heads=32, kv=8, hd=128, page_size=16)
    # one decode row at position 99: 100 keys, 7 pages
    f, b = pa.row_work(1, 99, **shape)
    assert f == 4 * 32 * 128 * 100
    assert b == 2 * 7 * 16 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    # a 16-token chunk at positions 32..47: keys 33 + ... + 48 = 648
    f, b = pa.row_work(16, 32, **shape)
    assert f == 4 * 32 * 128 * 648
    assert b == 2 * 3 * 16 * 8 * 128 * 2 + 2 * 16 * 32 * 128 * 2
    f2, b2 = pa.work([(1, 99), (16, 32)], **shape)
    assert f2 == 4 * 32 * 128 * 748


def test_least_time_is_the_larger_bound():
    from bench.peaks import least_time

    assert least_time(393e12, 1.0, 393e12, 819e9) == 1.0
    assert least_time(1.0, 819e9, 393e12, 819e9) == 1.0
