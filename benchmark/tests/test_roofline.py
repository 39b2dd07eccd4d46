"""Each roofline function against a count made by hand, and the shares that
are built from them cannot pass 100% by construction."""

import json
import os

from harness import common, readers
from roofline import flash, fused_adamw, paged_decode

PEAKS = json.load(open(os.path.join(common.BENCH, "harness", "peaks.json")))[
    "TPU v5 lite"]


def test_flash_counts():
    # B=1 H=1 S=4 D=2: one causal-half matmul = 2*4*4*2/2 = 32 FLOPs
    assert flash.matmul_flops(1, 1, 4, 2) == 32
    assert flash.fwd(1, 1, 4, 2)["flops"] == 64
    assert flash.bwd(1, 1, 4, 2)["flops"] == 160
    assert flash.fwd(1, 1, 4, 2)["bytes"] == 4 * 8 * 2 + 16
    # the 1.3B cell's forward call: 16 x 16 x 2048^2 x 128 x 2 = 275 GFLOP
    w = flash.fwd(16, 16, 2048, 128)
    assert abs(w["flops"] - 2 * 16 * 16 * 2048 * 2048 * 128) < 1
    t, bound = flash.min_seconds(w, PEAKS)
    assert bound == "compute" and abs(t - w["flops"] / 197e12) < 1e-12


def test_fused_adamw_counts():
    w = fused_adamw.update(1000)
    assert w["bytes"] == 1000 * 14 and w["flops"] == 12000
    assert fused_adamw.min_seconds(w, PEAKS)[1] == "memory"


def test_paged_decode_counts():
    # 100 live tokens, 16 heads of 128, bf16: K and V once each
    w = paged_decode.call(100, 16, 128)
    assert w["bytes"] == 2 * 100 * 16 * 128 * 2
    assert w["flops"] == 4 * 100 * 16 * 128
    assert paged_decode.min_seconds(w, PEAKS)[1] == "memory"


def test_required_flops_per_token_of_the_1p3b_cell():
    m = common.load_json("configs", "gpt3-1p3b-train.json")["model"]
    f = readers.required_flops_per_token(m, 2048)
    blocks = 24 * 12 * 2048 * 2048
    head = 50304 * 2048
    assert f == 6 * (blocks + head) + 24 * 6 * 2048 * 2048
    assert abs(f / 1e9 - 8.47) < 0.01
    # at the chip's peak one chip trains 197e12 / f tokens/s: mfu 100%
    assert 197e12 / f < 23300
