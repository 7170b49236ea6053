"""The byte count of decode_step_roofline against a hand-worked figure."""

import dataclasses
import json
import os

import pytest

from benchmark import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark/configs/mistral-7b-int8-1chip.json")) as f:
    MISTRAL = json.load(f)


def test_mistral_7b_int8_weight_bytes_by_hand():
    # One layer: wqkv 4096 x 6144, wo 4096 x 4096, gate/up/down 3 x 4096 x 14336.
    layer = 4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336
    assert layer == 218_103_808
    matrices = 32 * layer + 4096 * 32000  # + the output head, 1 byte each
    assert matrices == 7_110_393_856
    scales = (32 * (6144 + 4096 + 2 * 14336 + 4096) + 32000) * 2  # bf16
    norms = (2 * 32 * 4096 + 4096) * 2
    assert roofline.decode_weight_bytes(MISTRAL) == matrices + scales + norms
    assert roofline.decode_weight_bytes(MISTRAL) == pytest.approx(7.113e9, rel=1e-3)


def test_kv_bytes_per_token_is_128_kib():
    assert roofline.kv_bytes_per_token(MISTRAL) == 2 * 32 * 8 * 128 * 2 == 131072


def test_bf16_over_four_chips_is_a_quarter_of_twice_the_bytes():
    dense = dict(MISTRAL, weights="bf16")
    per_chip = roofline.decode_weight_bytes(dense, chips=4)
    assert per_chip == pytest.approx(7_110_393_856 * 2 / 4, rel=1e-3)
    assert roofline.kv_bytes_per_token(dense, chips=4) == 131072 / 4


@dataclasses.dataclass
class Call:
    prompt: list
    completion_tokens: int


def test_live_tokens_and_the_floor():
    # 8 rows, each prompt 100, 50 tokens decoded: a step reads on
    # average 100 + 25.5 cached tokens a row.
    calls = [Call([0] * 100, 50) for _ in range(8)]
    live = roofline.live_tokens_per_step(calls, decode_steps=50)
    assert live == pytest.approx(8 * 125.5)
    floor = roofline.decode_step_floor_ms(MISTRAL, "TPU v5 lite", live)
    want = (roofline.decode_weight_bytes(MISTRAL) + live * 131072) / 819e9 * 1e3
    assert floor == pytest.approx(want) and 8.6 < floor < 9.0
    # PR 24 read 34.9 ms a step: the share is about a quarter, never over 100.
    assert 24 < 100 * floor / 34.939 < 27


def test_an_unknown_chip_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
