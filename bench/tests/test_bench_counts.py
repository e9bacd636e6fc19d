"""FLOPs and bytes of a decode step, worked out by hand for both
configurations (GQA and MHA), and the table of peaks."""
import json
import pathlib

import pytest

from bench import arch, counts
from bench.tests import tiny

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_nemo_decode_step_by_hand():
    cfg = config("mistral-nemo-12b")
    # one layer: wq 5120*4096 + wk, wv 2*5120*1024 + wo 4096*5120
    # + SwiGLU 3*5120*14336 + two norms 2*5120
    assert arch.of(cfg).layer_params(cfg) == (
        20_971_520 + 10_485_760 + 20_971_520 + 220_200_960
        + 10_240) == 272_640_000
    work = counts.decode_step(cfg, [1000] * 8)
    # matmul weights per token: 8 layers without norms + head 5120*131072
    # = 2_181_038_080 + 671_088_640; x2 FLOPs x8 slots; attention
    # 8 layers * 4 * 32 heads * 128 * (8 slots * 1001 positions)
    assert work["flops"] == 45_634_027_520 + 1_049_624_576
    # weights read once: (8 * 272_640_000 + final norm 5120 + head
    # 671_088_640) * 2 bytes = 5_704_427_520; K/V read 8 layers * 2 *
    # 8 kv * 128 * 8000 * 2 = 262_144_000; K/V written 8 * 2 * 8 * 128
    # * 8 * 2 = 262_144; embedding rows 8 * 5120 * 2 and logits 8 *
    # 131072 * 2 = 2_179_072
    assert work["bytes"] == (5_704_427_520 + 262_144_000 + 262_144
                             + 2_179_072)


def test_deepseek_decode_step_by_hand():
    cfg = tiny.MHA
    # MHA: q, k, v, o all 4096*4096; SwiGLU 3*4096*11008; norms 2*4096
    assert arch.of(cfg).layer_params(cfg) == (
        67_108_864 + 135_266_304 + 8_192) == 202_383_360
    work = counts.decode_step(cfg, [2000])
    # 2 * (6 * 202_375_168 + 4096 * 102400) + 6 * 4 * 32 * 128 * 2001
    assert work["flops"] == 3_267_362_816 + 196_706_304
    # weights (6 * 202_383_360 + 4096 + 419_430_400) * 2; K/V read
    # 6 * 2 * 4096 * 2000 * 2; written 6 * 2 * 4096 * 2; embedding row
    # and logits (4096 + 102400) * 2
    assert work["bytes"] == (3_267_469_312 + 196_608_000 + 98_304
                             + 212_992)


def test_least_seconds_takes_the_larger_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flop_per_s"] == 197e12
    assert peak["hbm_byte_per_s"] == 819e9
    mem = {"flops": 197e9, "bytes": 819e9 * 2}
    assert counts.least_seconds(mem, peak) == pytest.approx(2.0)
    comp = {"flops": 197e12 * 3, "bytes": 1.0}
    assert counts.least_seconds(comp, peak) == pytest.approx(3.0)


def test_a_device_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("cpu")
