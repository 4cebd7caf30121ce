"""Operation and byte counts on shapes worked out by hand."""
import pytest

from chipbench import flops
from chipbench.peaks import peaks_for

A = {"model_type": "qwen2", "hidden_size": 64, "num_attention_heads": 4,
     "num_key_value_heads": 2, "intermediate_size": 128, "vocab_size": 100,
     "num_hidden_layers": 3}


def test_arch_and_dense():
    a = flops.arch(A)
    assert a["hd"] == 16
    # q 64x64, k/v 64x32 each, o 64x64, mlp 3 x 64x128 -> per layer
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert flops.token_flops(a) == 2 * 3 * per_layer
    assert flops.head_flops(a) == 2 * 100 * 64


def test_prefill_keys_are_causal():
    assert flops.prefill_keys(0, 1) == 1
    assert flops.prefill_keys(0, 4) == 1 + 2 + 3 + 4
    assert flops.prefill_keys(10, 2) == 11 + 12


def test_decode_attention_cost():
    a = flops.arch(A)
    ops, nbytes = flops.decode_attn_cost(a, [9, 19])    # 10 + 20 keys
    assert ops == 4 * 3 * 4 * 16 * 30
    kv = 2 * 30 * 2 * 16 * 4          # K and V of every key, 2 kv heads
    qo = 2 * 2 * 4 * 16 * 4           # q in and out for 2 rows, 4 heads
    assert nbytes == 3 * (kv + qo)


def test_prefill_attention_cost():
    a = flops.arch(A)
    ops, nbytes = flops.prefill_attn_cost(a, [(0, 4), (10, 2)])
    assert ops == 4 * 3 * 4 * 16 * (10 + 23)
    by = (2 * 4 * 2 * 16 * 4 + 2 * 4 * 4 * 16 * 4
          + 2 * 12 * 2 * 16 * 4 + 2 * 2 * 4 * 16 * 4)
    assert nbytes == 3 * by


def test_step_flops_adds_up():
    a = flops.arch(A)
    got = flops.step_flops(a, [9], [(0, 4)])
    want = (5 * flops.token_flops(a)
            + flops.attn_flops(a, 10 + 10) + 2 * flops.head_flops(a))
    assert got == want


def test_roofline_picks_the_larger_bound():
    peak = peaks_for("TPU v5 lite")
    t, bound = flops.roofline_time(197e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = flops.roofline_time(1.0, 819e9, peak)
    assert bound == "bandwidth" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
