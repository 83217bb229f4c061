"""The FLOP and byte functions, against values worked out by hand at both
configurations' sizes."""

from bench import costs


def test_lm_matmul_params_internlm2_2l():
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # MLP 3 x 2048x8192; plus the head 2048 x 11568
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert layer == 62_914_560
    n = costs.lm_matmul_params(2048, 2, 16, 8, 128, 8192, 11568)
    assert n == 2 * layer + 2048 * 11568 == 149_520_384


def test_lm_train_flops_internlm2_2l():
    f = costs.lm_train_flops(d_model=2048, n_layers=2, n_heads=16,
                             n_kv_heads=8, head_dim=128, d_ff=8192,
                             vocab=11568, batch=4, seq=2048)
    dense = 6 * 149_520_384 * 4 * 2048
    pairs = 2048 * 2049 // 2            # causal query-key pairs
    attn = 3 * (2 * 2 * 128 * 16 * pairs * 2) * 4
    assert f == dense + attn
    assert abs(f - 7.7617e12) / 7.7617e12 < 1e-4


def test_dirty_diff_bytes_hacc():
    # 100,001,792 particles: 7 float fields of 400,007,168 B, pid
    # 800,014,336 B, mask 200,003,584 B; all whole 4 KiB pages
    shards = [400_007_168] * 7 + [800_014_336, 200_003_584]
    pages = sum(shards) // 4096
    assert pages == 927_751
    assert costs.dirty_diff_bytes(shards) == 2 * sum(shards) + 4 * pages


def test_pack_rows_bytes():
    assert costs.pack_rows_bytes([8192], 0) == 2 * 4
    assert costs.pack_rows_bytes([], 3) == 2 * 3 * 4096
    # a partial page counts whole
    assert costs.pack_rows_bytes([4097], 1) == 2 * 4 + 2 * 4096
