"""FLOP and byte counts against values worked out by hand for one prefill
and one decode shape of each configuration, at its served sizes."""
import json

import smoke  # noqa: F401
import flops
import peaks
import run


def _sizes(name):
    with open(run.ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)["model"]


def test_yi_9b_24l():
    s = _sizes("yi-9b-24L")
    # per layer and token: q 4096x4096, k and v 4096x512 each, o 4096x4096,
    # SwiGLU 3 x 4096x11008; two operations per multiply-add
    layer = 2 * (4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008)
    assert layer == 346_030_080
    head = 2 * 4096 * 64000
    n = 2560
    attn = 24 * 2 * 32 * 128 * n * (n + 1)   # causal QK^T and PV
    assert flops.prefill_flops(s, n) == n * 24 * layer + attn + head
    assert flops.prefill_flops(s, n) == 22_549_605_908_480
    ctx = [2000, 3000]
    assert flops.decode_flops(s, ctx) == 2 * (24 * layer + head) + 24 * 4 * 32 * 128 * 5000
    weights = 2 * (24 * (4096 * (4096 + 1024) + 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096)
                   + 4096 + 64000 * 4096)
    kv = 24 * 2 * 4 * 128 * 2
    assert kv == 49_152
    assert flops.decode_bytes(s, ctx) == weights + 2 * 4096 * 2 + kv * 5000 + kv * 2
    assert flops.decode_bytes(s, ctx) == 9_075_286_016


# granite-34b-code-base at 11 of its 88 layers (hf
# ibm-granite/granite-34b-code-base): the GELU-MLP, one-KV-head shape that
# the counts also cover.
GRANITE_34B_11L = {"num_layers": 11, "d_model": 6144, "num_heads": 48, "num_kv_heads": 1,
                   "head_dim": 128, "d_ff": 24576, "vocab_size": 49152,
                   "mlp_type": "gelu"}


def test_granite_34b_11l():
    s = GRANITE_34B_11L
    # q 6144x6144, k and v 6144x128 each (one KV head), o 6144x6144, GELU
    # MLP 2 x 6144x24576
    layer = 2 * (6144 * 6144 + 2 * 6144 * 128 + 6144 * 6144 + 2 * 6144 * 24576)
    assert layer == 758_120_448
    head = 2 * 6144 * 49152
    n = 3072
    attn = 11 * 2 * 48 * 128 * n * (n + 1)
    assert flops.prefill_flops(s, n) == n * 11 * layer + attn + head
    ctx = [4000]
    assert flops.decode_flops(s, ctx) == 11 * layer + head + 11 * 4 * 48 * 128 * 4000
    kv = 11 * 2 * 1 * 128 * 2
    assert kv == 5_632
    weights = 2 * (11 * (6144 * (6144 + 256) + 6144 * 6144 + 2 * 6144 * 24576 + 2 * 6144)
                   + 6144 + 49152 * 6144)
    assert flops.decode_bytes(s, ctx) == weights + 2 * 6144 + kv * 4000 + kv


def test_roofline_takes_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    assert flops.roofline_s(197e12, 1.0, p) == 1.0
    assert flops.roofline_s(1.0, 819e9 * 2, p) == 2.0


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        peaks.peaks("cpu")
