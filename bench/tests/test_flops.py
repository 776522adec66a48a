import pytest

from bench import flops

# L=2 layers, d=8, 2 query heads, 1 KV head, head dim 4, d_ff 16, vocab 10
ARCH = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab": 10}


def test_matmul_macs_hand_count():
    # per layer: q 8*2*4=64, k+v 2*8*1*4=64, o 2*4*8=64, mlp 3*8*16=384
    assert flops.matmul_macs_per_token(ARCH) == 2 * (64 + 64 + 64 + 384) + 80


def test_causal_pairs_counts_the_lower_triangle():
    for s in (1, 2, 3, 7):
        assert flops.causal_pairs(s) == sum(
            1 for i in range(s) for j in range(s) if j <= i)


def test_prefill_and_flash_hand_count():
    f, b = flops.flash_prefill(ARCH, 3)
    # 6 causal pairs; q k^T and p v: 2 * 2 flops per pair per head-dim
    assert f == 2 * 4 * 2 * 4 * 6
    # q and o (2 heads) + k and v (1 head), 3 tokens, 4 dims, bf16
    assert b == 2 * 3 * 4 * (2 * 2 + 2 * 1) * 2
    assert flops.prefill_flops(ARCH, 3) == 2 * 1232 * 3 + f


def test_decode_hand_count():
    assert flops.decode_flops(ARCH, 5) == 2 * 1232 + 2 * 4 * 2 * 4 * 5
    # prompt 3, 3 tokens: the first from the prefill, then contexts 4, 5
    assert flops.request_decode_flops(ARCH, 3, 3) == (
        flops.decode_flops(ARCH, 4) + flops.decode_flops(ARCH, 5))
    assert flops.request_decode_flops(ARCH, 3, 1) == 0


def test_roofline_share_names_the_binding_bound():
    share, bound = flops.roofline_share(197e12, 1e9, 2.0, 197e12, 819e9)
    assert bound == "flops" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share(1e9, 819e9, 4.0, 197e12, 819e9)
    assert bound == "bytes" and share == pytest.approx(25.0)
