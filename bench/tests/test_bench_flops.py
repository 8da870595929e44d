"""The FLOP/byte counter against hand-computed values for one decode
step of one lane, at granite-3-2b's and starcoder2-3b's published
sizes, with the granite configuration file's prune settings."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, spec  # noqa: E402


def test_granite_decode_step():
    m = spec.config("granite-3-2b")["model"]
    f = flops.decode_step_flops(m, 128, 1088)
    # per layer: q 2048x2048, k and v 2048x512 each, o 2048x2048,
    # gate/up/down 3 x 2048x8192
    assert f["matmul"] == 40 * 2 * (2048 * 2048 * 2 + 2 * 2048 * 512
                                    + 3 * 2048 * 8192)
    assert f["matmul"] == 4_865_392_640
    assert f["lm_head"] == 2 * 2048 * 49155 == 201_338_880
    assert f["cam"] == 40 * 2 * 32 * 1088 * 64 == 178_257_920
    assert f["exact"] == 40 * 4 * 32 * 128 * 64 == 41_943_040
    work, nbytes = flops.decode_attention_work(m, 128, 1088)
    assert work == 178_257_920 + 41_943_040
    # int8 mirror 8x1088x64, winners' K and V 2x8x128x64 bf16, scales
    assert nbytes == 40 * (8 * 1088 * 64 + 2 * 8 * 128 * 64 * 2
                           + 8 * 1088 * 4) == 34_160_640


# starcoder2-3b's published sizes (arXiv:2402.19173; hf bigcode/starcoder2-3b)
STARCODER2_3B = {"num_hidden_layers": 30, "hidden_size": 3072,
                 "num_attention_heads": 24, "num_key_value_heads": 2,
                 "head_dim": 128, "intermediate_size": 12288,
                 "vocab_size": 49152, "hidden_act": "gelu_pytorch_tanh"}


def test_starcoder2_decode_step():
    m = STARCODER2_3B
    f = flops.decode_step_flops(m, 128, 1040)
    # per layer: q 3072x3072, k and v 3072x256, o 3072x3072,
    # up/down 2 x 3072x12288 (GELU, no gate)
    assert f["matmul"] == 30 * 2 * (3072 * 3072 * 2 + 2 * 3072 * 256
                                    + 2 * 3072 * 12288) == 5_756_682_240
    assert f["lm_head"] == 2 * 3072 * 49152
    assert f["cam"] == 30 * 2 * 24 * 1040 * 128
    assert f["exact"] == 30 * 4 * 24 * 128 * 128
    _, nbytes = flops.decode_attention_work(m, 128, 1040)
    assert nbytes == 30 * (2 * 1040 * 128 + 2 * 2 * 128 * 128 * 2
                           + 2 * 1040 * 4)


def test_winners_capped_by_fill_and_fills_by_slots():
    m = spec.config("granite-3-2b")["model"]
    assert flops.decode_step_flops(m, 128, 20)["exact"] == \
        40 * 4 * 32 * 20 * 64
    # a 3000-token prompt keeps 1024 slots; its decode steps attend over
    # 1025, 1026, ... and stop growing at the 1088 slots
    fills = flops.decode_fills(3000, 100, 1024, 1088)
    assert fills[:2] == [1025, 1026] and fills[-1] == 1088
    assert len(fills) == 99
    assert flops.decode_fills(100, 1, 1024, 1088) == []


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    assert flops.roofline_seconds(2e12, 8e9, peak) == 0.01
    assert flops.roofline_seconds(4e12, 8e9, peak) == 0.02
