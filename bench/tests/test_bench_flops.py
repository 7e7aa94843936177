"""The FLOP counts of both configurations against hand counts."""
import json

from bench_testlib import BENCH
from harness import flops


def arch(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["arch"]


def test_granite_decode_token():
    a = arch("granite-moe-3b-a800m")
    # attention weights 1536*24*64 + 2*1536*8*64 + 24*64*1536 = 6,291,456;
    # router 1536*40 = 61,440; 8 experts of 3*1536*512 = 18,874,368
    assert flops.layer_matmul_flops(a) == 2 * (6_291_456 + 61_440 + 18_874_368)
    # QK^T and PV over 513 keys: 4 * 24 * 64 * 513
    assert flops.layer_mixing_flops(a, 512) == 3_151_872
    assert flops.head_flops(a) == 2 * 1536 * 49155
    one = 32 * (50_454_528 + 3_151_872) + 151_004_160
    assert flops.decode_flops(a, 1, 512) == one == 1_866_408_960
    assert flops.decode_flops(a, 8, 512) == 8 * one


def test_granite_prefill_counts_causal_keys_and_one_head():
    a = arch("granite-moe-3b-a800m")
    # 4 prompt tokens: 1+2+3+4 = 10 keys, logits for the last token only
    want = 4 * 32 * 50_454_528 + 32 * 4 * 24 * 64 * 10 + 151_004_160
    assert flops.prefill_flops(a, 1, 4) == want == 6_611_149_824
    assert flops.prefill_flops(a, 3, 4) == 3 * want


def test_mamba2_decode_token():
    a = arch("mamba2-1.3b")
    # in_proj 2048*(2*4096 + 2*128 + 64) = 17,432,576; out_proj 4096*2048
    assert flops.layer_matmul_flops(a) == 2 * (17_432_576 + 8_388_608)
    # conv 2*4*(4096+256); scan 5 * 64 heads * 128 state * 64 headdim
    assert flops.layer_mixing_flops(a, 0) == 34_816 + 2_621_440
    assert flops.layer_mixing_flops(a, 9999) == flops.layer_mixing_flops(a, 0)
    one = 48 * (51_642_368 + 2_656_256) + 2 * 2048 * 50277
    assert flops.decode_flops(a, 16, 600) == 16 * one == 16 * 2_812_268_544
    assert flops.prefill_flops(a, 1, 4) == 4 * 48 * (51_642_368 + 2_656_256) \
        + 2 * 2048 * 50277
