"""Operations and bytes from shapes, against hand counts; the peaks
table."""
import json

import pytest

import peaks
import work
from conftest import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_smollm_ops_per_token_by_hand():
    # per layer: wq 576x576, wk/wv 576x192, wo 576x576, gate/up
    # 576x1536, down 1536x576; 30 layers; unembedding 576x49152
    layer = 576 * 576 * 2 + 576 * 192 * 2 + 576 * 1536 * 3
    assert layer == 3_538_944
    assert work.ops_per_token(config("smollm-135m")) == 2 * (30 * layer + 576 * 49152)


def test_mamba2_ops_per_token_by_hand():
    # in_proj 1536 x (2*3072 + 2*128 + 48) = 1536 x 6448, out 3072 x 1536
    layer = 1536 * 6448 + 3072 * 1536
    assert layer == 14_622_720
    assert work.ops_per_token(config("mamba2-780m")) == 2 * (48 * layer + 1536 * 50280)


def with_activations(c, ternary):
    q = dict(c["served"]["quant"], quantize_activations=ternary)
    return dict(c, served=dict(c["served"], quant=q))


def test_cim_call_counts_bf16_activations_two_bit_weights_bf16_output():
    c = config("smollm-135m")
    assert not work.ternary_activations(c)
    ops, nbytes = work.cim_call(c, 64, 576, 1536)
    assert ops == 2 * 64 * 576 * 1536
    assert nbytes == 64 * 576 * 2 + 576 * 1536 // 4 + 64 * 1536 * 2


def test_cim_call_counts_ternary_activations_at_two_bits():
    c = with_activations(config("smollm-135m"), True)
    ops, nbytes = work.cim_call(c, 64, 576, 1536)
    assert ops == 2 * 64 * 576 * 1536
    assert nbytes == (64 * 576 + 576 * 1536) // 4 + 64 * 1536 * 2


def test_ops_peak_follows_the_operand_types():
    p = peaks.peaks("TPU v5 lite")
    c = config("mamba2-780m")
    assert work.ops_peak(c, p) == 197e12
    assert work.ops_peak(with_activations(c, True), p) == 393e12


def test_cim_step_sums_every_layer():
    c = config("mamba2-780m")
    ops, nbytes = work.cim_step(c, 32)
    o1, b1 = work.cim_call(c, 32, 1536, 6448)
    o2, b2 = work.cim_call(c, 32, 3072, 1536)
    assert (ops, nbytes) == (48 * (o1 + o2), 48 * (b1 + b2))


def test_least_time_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.least_time(393e12, 1.0, 393e12, p)
    assert (t, bound) == (1.0, "ops")
    t, bound = work.least_time(1.0, 819e9, 197e12, p)
    assert (t, bound) == (1.0, "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["int8_ops"] == 393e12
