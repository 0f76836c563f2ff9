"""Each cost function against a count made by hand at the configured
shapes."""
import json

import pytest

from benchtools import BENCH
from harness import spec

B = spec.Bench(BENCH.parent)


def _dims(config):
    conf = B.config(config)
    return B.reference(conf["family"]).dims(conf)


def test_model_step_gpt2_decode_row():
    # one row with 99 cached positions: 100 keys
    # matmuls 2 * 24 * (4 * 1024^2 + 2 * 1024 * 4096) = 603,979,776
    # head 2 * 1024 * 50257 = 102,926,336
    # attention 24 * 4 * 16 * 64 * 100 = 9,830,400
    f = B.cost("model_step").flops({"kind": "decode", "lens": [99]},
                                   _dims("gpt2-345m-w8a8"))
    assert f == 603_979_776 + 102_926_336 + 9_830_400


def test_model_step_minitron_prefill_chunk():
    # 32 tokens at positions 32..63: keys 32*32 + 32*33/2 = 1,552
    # per-layer params 3072*3072*2 + 2*3072*1024 + 2*3072*9216 = 81,788,928
    d = _dims("minitron-4b-bf16")
    f = B.cost("model_step").flops({"kind": "prefill", "start": 32,
                                    "n": 32}, d)
    mm = 2 * 16 * 81_788_928 * 32
    head = 2 * 3072 * 256_000
    attn = 16 * 4 * 24 * 128 * 1552
    assert f == mm + head + attn


def test_paged_attn_gpt2_decode_row():
    d = _dims("gpt2-345m-w8a8")
    f, b = B.cost("paged_attn").cost({"kind": "decode", "lens": [99]}, d, 4)
    assert f == 9_830_400
    # K and V of 100 keys in bf16, q and out of 16 heads in f32, 24 layers
    assert b == (2 * 16 * 64 * 100 * 2 + 2 * 16 * 64 * 4) * 24


def test_paged_attn_minitron_prefill_chunk():
    d = _dims("minitron-4b-bf16")
    f, b = B.cost("paged_attn").cost({"kind": "prefill", "start": 32,
                                      "n": 32}, d, 2)
    assert f == 4 * 24 * 128 * 1552 * 16
    # K and V of 64 live positions over 8 KV heads; 32 queries of 24 heads
    assert b == (2 * 8 * 128 * 64 * 2 + 2 * 24 * 128 * 32 * 2) * 16


def test_quant_matmul_gpt2_decode_two_rows():
    d = _dims("gpt2-345m-w8a8")
    o, b = B.cost("quant_matmul").cost({"kind": "decode", "lens": [5, 9]},
                                       d)
    assert o == 2 * 2 * (4 * 1024 * 1024 + 2 * 1024 * 4096) * 24
    per_layer = (12_582_912  # int8 weights
                 + 4 * 9216  # column scales
                 + 2 * 9216  # int8 activations in
                 + 4 * 2 * 6  # row scales
                 + 4 * 2 * 9216)  # f32 outputs
    assert b == per_layer * 24


@pytest.mark.parametrize("kernel", ["paged_attn", "quant_matmul"])
def test_bound_is_the_larger_of_compute_and_memory(kernel):
    d = _dims("gpt2-345m-w8a8")
    peak = B.peaks()["devices"]["TPU v5 lite"]
    call = {"kind": "decode", "lens": [500] * 32}
    eng = {"precision": "w8a8"}
    t = B.cost(kernel).bound_s(call, d, eng, peak)
    assert t > 0
    assert json.dumps(t)


def test_a_family_cost_file_is_preferred(tmp_path):
    """``costs/<kernel>.<family>.py`` is read where it exists; every other
    family falls back to ``costs/<kernel>.py``."""
    from benchtools import make_root

    root = make_root(tmp_path)
    (root / "bench" / "costs" / "model_step.fam.py").write_text(
        "def flops(call, dims):\n    return 1.0\n")
    bench = spec.Bench(root)
    call = {"kind": "decode", "lens": [99]}
    d = _dims("gpt2-345m-w8a8")
    assert bench.cost("model_step", "fam").flops(call, d) == 1.0
    for family in (None, "gpt2", "nemotron", "no-such-family"):
        assert bench.cost("model_step", family).flops(call, d) == \
            603_979_776 + 102_926_336 + 9_830_400
    assert bench.cost("paged_attn", "gpt2").cost(call, d, 4) == \
        B.cost("paged_attn").cost(call, d, 4)
    assert bench.cost("quant_matmul", "gpt2").cost(call, d) == \
        B.cost("quant_matmul").cost(call, d)


def test_model_step_olmoe_counts_routed_experts(tiny_root):
    """The sparse-expert family's step counts the router over all 8
    experts and the three matrices of the 2 a token is routed to."""
    bench = spec.Bench(tiny_root)
    conf = bench.config("tiny-olmoe")
    d = bench.reference(conf["family"]).dims(conf)
    cost = bench.cost("model_step", "olmoe")
    # per layer: attention 4 * 64 * 64 = 16,384; router 64 * 8 = 512;
    # experts 2 * 3 * 64 * 32 = 12,288
    # one row with 9 cached positions: 10 keys, 2 layers
    mm = 2 * 2 * (16_384 + 512 + 12_288)
    head = 2 * 64 * 512
    attn = 2 * 4 * 4 * 16 * 10
    f = cost.flops({"kind": "decode", "lens": [9]}, d)
    assert f == mm + head + attn


def test_model_step_llama_counts_the_gated_mlp(tiny_root):
    """The llama family's step counts up, gate and down for every token;
    its dims carry no ``mlp_matrices``, which the general file needs."""
    bench = spec.Bench(tiny_root)
    conf = bench.config("tiny-llama")
    d = bench.reference(conf["family"]).dims(conf)
    assert "mlp_matrices" not in d
    # per layer: q, out 64 * 64; k, v 64 * 32; up, gate, down 64 * 128
    per_layer = 2 * 4096 + 2 * 2048 + 3 * 8192
    # 8 tokens at positions 16..23: keys 8 * 16 + 8 * 9 / 2 = 164
    f = bench.cost("model_step", "llama").flops(
        {"kind": "prefill", "start": 16, "n": 8}, d)
    assert f == 2 * 2 * per_layer * 8 + 2 * 64 * 512 + 2 * 4 * 4 * 16 * 164
