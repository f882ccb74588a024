"""FLOP and byte counts at deepseek-7b widths against hand-worked figures."""
import json
from pathlib import Path

import pytest

from chipbench import counts

CONF = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "deepseek-7b-split-serve.json").read_text())
M = CONF["model"]


def test_layer_weights():
    # q 4096*4096, k and v 4096*4096 each, o 4096*4096, gate/up/down 3*4096*11008
    assert counts.layer_matmul_params(M) == 4 * 16_777_216 + 135_266_304 == 202_375_168


@pytest.mark.parametrize("ctx,head,want", [
    # 4 layers x (2 x 202,375,168 + 4*32*128*ctx)
    (1, False, 4 * (404_750_336 + 16_384)),
    (768, False, 4 * (404_750_336 + 16_384 * 768)),
    # + the head, 2 * 4096 * 102400
    (1, True, 4 * (404_750_336 + 16_384) + 838_860_800),
])
def test_token_flops(ctx, head, want):
    assert counts.token_flops(M, ctx, head=head) == want


def test_paged_attention_call():
    ops, byts = counts.paged_attention_call(M, [0, 99])
    assert ops == 4 * 32 * 128 * (1 + 100) == 1_654_784
    # K and V of 101 positions (32 heads x 128 x 4 B each) + q and out
    assert byts == 2 * 101 * 16_384 + 2 * 2 * 16_384 == 3_375_104


def test_circconv_call():
    ops, byts = counts.circconv_call(4, 4, 4096)
    assert byts == (4 * 4 + 4 + 4) * 4096 * 4 == 393_216
    assert ops == (4 * 5 + 4) * 2.5 * 4096 * 12 == 2_949_120
