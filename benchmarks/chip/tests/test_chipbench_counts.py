"""FLOP and byte counts against hand counts."""
import pytest

from harness import counts

# a small model whose counts are easy by hand
M = dict(hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
         head_dim=2, intermediate_size=8, vocab_size=10, num_hidden_layers=3)


def test_layer_and_head_flops():
    # q 2*4*4, k,v 2*4*2 each, o 2*4*4 -> 32+16+16+32 = 96; ffn 3*2*4*8
    assert counts.layer_matmul_flops(M) == 96 + 192
    assert counts.layer_matmul_flops(M, keep_ffn=0.5) == 96 + 96
    assert counts.attn_flops(M, 5) == 4 * 2 * 2 * 5
    assert counts.head_flops(M) == 2 * 4 * 10


def test_train_flops_per_token():
    fwd = 3 * (96 + 96 + 4 * 2 * 2 * 2.5) + 80        # seq 4: keys 2.5
    assert counts.train_flops_per_token(M, 4, 0.5) == pytest.approx(3 * fwd)


def test_serve_tick_flops_counts_real_tokens_only():
    # slot 0 decodes at position 10 (11 keys); slot 1 prefills 3 tokens
    # from 0 (1 + 2 + 3 keys); an idle slot adds nothing
    want = 3 * (96 + 192) + 3 * 16 * 11 + 80 \
        + 3 * (3 * (96 + 192)) + 3 * 16 * 6 + 80
    got = counts.serve_tick_flops(M, [(10, 1), (0, 3), (5, 0)])
    assert got == pytest.approx(want)


def test_paged_attn_call():
    flops, nbytes = counts.paged_attn_call(M, [(10, 1), (0, 3)], page_size=4)
    assert flops == 16 * 11 + 16 * 6
    # pages: ceil(11/4)=3 and ceil(3/4)=1; K+V bf16: 2*pages*1*4*2*2
    kv = 2 * 3 * 4 * 2 * 2 + 2 * 1 * 4 * 2 * 2
    qo = 2 * 1 * 2 * 2 * 2 + 2 * 3 * 2 * 2 * 2
    assert nbytes == kv + qo


def test_dropout_matmul_call_skips_dropped_blocks():
    flops, nbytes = counts.dropout_matmul_call(
        rows=8, k=16, n=32, block_n=8, kept_blocks=[4, 2])
    assert flops == 2 * 8 * 16 * 8 * (4 + 2)
    assert nbytes == 2 * 2 * 8 * 16 + 2 * 16 * 32 + 4 * 2 * 8 * 32


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert counts.roofline_s(1000, 20, peaks) == 10.0
    assert counts.roofline_s(100, 50, peaks) == 5.0
