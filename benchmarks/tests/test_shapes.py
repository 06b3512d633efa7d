"""The ``llama`` family's FLOP and byte functions against hand counts: the
five counts through the loaded family, as the readers call them, and what
they are made of through its ``shapes`` module."""
import json
import os

import pytest

import families
from conftest import BENCH
from families.llama import shapes


def conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_resident_planes_are_4_45_gb():
    m = conf("mistral-7b-v0.3-q40")
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert shapes.resident_weights_per_layer(m) == per_layer
    weights = 32 * per_layer + 4096 * 32768
    assert weights == pytest.approx(7.11e9, rel=2e-3)
    assert weights * 0.625 == pytest.approx(4.45e9, rel=2e-3)
    fam = families.load(m)
    assert fam.plane_bytes_per_launch(m, 8) == pytest.approx(4.45e9, rel=2e-3)
    assert fam.resident_bytes(m) == pytest.approx(4.45e9 + 4 * 32768 * 4096, rel=2e-3)
    assert shapes.active_weights_per_token(m) == weights


def test_mixtral_layer_is_0_907_gb_and_two_experts_are_active():
    m = conf("mixtral-8x7b-d10-q40")
    fam = families.load(m)
    attn = 4096 * 6144 + 4096 * 4096
    expert = 3 * 4096 * 14336
    assert shapes.resident_weights_per_layer(m) == attn + 8 * expert
    assert (attn + 8 * expert) * 0.625 == pytest.approx(0.907e9, rel=2e-3)
    active = 10 * (attn + 2 * expert + 4096 * 8) + 4096 * 32000
    assert shapes.active_weights_per_token(m) == active
    # the least a launch reads: attention, the experts its rows need,
    # classifier, router. One row needs its 2 experts; 8 rows choosing
    # evenly need 8 (1 - 0.75^8) = 7.2 of the 8; a 64-token piece all 8
    assert shapes.experts_needed(m, 1) == 2.0
    assert shapes.experts_needed(m, 8) == pytest.approx(8 * (1 - 0.75 ** 8))
    assert shapes.experts_needed(m, 64) == pytest.approx(8.0, rel=1e-6)
    assert shapes.experts_needed(conf("mistral-7b-v0.3-q40"), 8) == 1.0
    for rows in (1, 8):
        need = shapes.experts_needed(m, rows)
        least = (10 * (attn + need * expert) + 4096 * 32000) * 0.625 + 10 * 4096 * 8 * 4
        assert fam.plane_bytes_per_launch(m, rows) == pytest.approx(least)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    q40 = (10 * (attn + 8 * (1 - 0.75 ** 8) * expert) + 4096 * 32000) * 0.625
    assert fam.launch_least_seconds(m, 8, peaks) == pytest.approx(q40 / 819e9)
    assert fam.resident_bytes(m) == pytest.approx(9.68e9, rel=5e-3)


def test_flops_and_kv():
    m = conf("mistral-7b-v0.3-q40")
    assert shapes.kv_bytes_per_position(m) == 2 * 32 * 1024 * 2  # 131 KB
    fam = families.load(m)
    f0 = fam.flops_per_token(m, 0)
    assert f0 == 2 * shapes.active_weights_per_token(m)
    assert fam.flops_per_token(m, 1000) - f0 == 4 * 4096 * 1000 * 32
    # every layer attends over the whole context: what ``bytes_share`` read
    # before the family stood between it and the count
    for context in (0, 1, 277.5, 4096):
        assert fam.kv_read_bytes(m, context) == context * shapes.kv_bytes_per_position(m)


def test_least_seconds_turns_from_bytes_to_flops():
    m = conf("mistral-7b-v0.3-q40")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    w = shapes.active_weights_per_token(m)
    least = families.load(m).launch_least_seconds
    assert least(m, 8, peaks) == pytest.approx(w * 0.625 / 819e9)
    assert least(m, 2048, peaks) == pytest.approx(2 * 2048 * w / 197e12)
