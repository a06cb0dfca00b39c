"""FLOP and byte counts against hand counts at small sizes, and the
peaks table."""
import pytest

import flops

DENSE = {"hidden_size": 8, "num_hidden_layers": 1, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32,
         "intermediate_size": 16}


def test_dense_forward_matches_a_hand_count():
    # T = 4 tokens: q 2*4*8*8, k and v 2*4*8*4 each, o 2*4*8*8
    proj = 512 + 256 + 256 + 512
    # query i sees i + 1 keys: 10 dot products of 4 per head, for q.k and
    # for the weighted values, 2 heads
    attn = 2 * (2 * 4 * 10) * 2
    mlp = 3 * (2 * 4 * 8 * 16)
    head = 2 * 4 * 8 * 32
    assert flops.forward_flops(DENSE, 1, 4) == proj + attn + mlp + head
    assert flops.train_step_flops(DENSE, 1, 4) == 3 * (proj + attn + mlp
                                                       + head)


def test_window_limits_the_keys_each_query_sees():
    full = flops.forward_flops(DENSE, 1, 4)
    windowed = flops.forward_flops({**DENSE, "sliding_window": 2}, 1, 4)
    # keys seen 1, 2, 2, 2 instead of 1, 2, 3, 4: 3 fewer per head
    assert full - windowed == 2 * (2 * 4 * 3) * 2


def test_moe_counts_the_router_and_the_routed_experts_only():
    moe = {**DENSE, "num_local_experts": 4, "num_experts_per_tok": 2}
    dense = flops.forward_flops(DENSE, 1, 4)
    router = 2 * 4 * 8 * 4
    experts = 4 * 2 * 3 * (2 * 8 * 16)      # tokens x top-k x 3 matmuls
    assert flops.forward_flops(moe, 1, 4) == (dense - 3 * (2 * 4 * 8 * 16)
                                              + router + experts)


def test_layers_and_batch_scale_the_count():
    two = {**DENSE, "num_hidden_layers": 2}
    head = 2 * 4 * 8 * 32
    one = flops.forward_flops(DENSE, 1, 4)
    assert flops.forward_flops(two, 1, 4) == 2 * (one - head) + head
    assert flops.forward_flops(DENSE, 3, 4) == 3 * one


def test_hlo_shape_bytes_counts_results_and_operands():
    text = ("(f32[8,128]{1,0:T(8,128)}, f32[8,128]{1,0}) custom-call("
            "f32[4,4]{1,0} %a, s8[4,8,128]{2,1,0} %b, bf16[2]{0} %c")
    assert flops.hlo_shape_bytes(text) == 4096 + 4096 + 64 + 4096 + 4


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        flops.peaks("TPU v9 imaginary")
