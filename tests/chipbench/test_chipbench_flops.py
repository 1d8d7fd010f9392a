"""The operation and byte counts against hand-worked values."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, harness  # noqa: E402


def test_attention_block_by_hand():
    # 2 query and 3 key positions of one sequence, d_model 4:
    # projections 2*4*4*(2*2 + 2*3) = 320, dots 2*2*2*3*4 = 96
    assert flops.attention_fwd_flops(2, 3, 3, 4, False) == 416
    assert flops.attention_fwd_flops(2, 2, 2, 4, True) == 2 * 16 * 8 + 32


def test_ffn_by_hand():
    assert flops.ffn_fwd_flops(3, 4, 16) == 2 * 2 * 3 * 4 * 16


def test_transformer_big_step_by_hand():
    # per token and layer: encoder 8 M^2 + 4 T M + 4 M F; decoder
    # 16 M^2 + 2 T M (causal half) + 4 T M + 4 M F; head 2 M V; x3
    m, f, t, v, n = 1024, 4096, 512, 32000, 6
    enc = 8 * m * m + 4 * t * m + 4 * m * f
    dec = 16 * m * m + 6 * t * m + 4 * m * f
    per_token = 3 * (n * (enc + dec) + 2 * m * v)
    got = flops.encdec_train_flops_per_step(16, t, t, m, f, n, v)
    assert got == pytest.approx(per_token * 16 * t, rel=1e-12)
    assert got / (16 * t) == pytest.approx(1.348e9, rel=1e-3)


def test_kv_gather_bytes_of_the_decode_cell():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gpt2_medium_d12.json")) as f:
        b = json.load(f)["build"]
    rows = b["n_slots"] * (b["prompt_len"] + b["max_new"])
    assert rows * b["n_layer"] == 589824       # PR 22's geometry, ISSUE 23
    got = flops.kv_gather_bytes_per_step(rows, b["d_model"], 4, b["n_layer"])
    assert got == 589824 * 4096 * 2 * 2          # K and V, read and write


@pytest.mark.parametrize("fl,by,sec,want", [
    (197e12, 0.0, 2.0, 50.0),        # compute-bound: 1 s least of 2 s
    (0.0, 819e9, 4.0, 25.0),         # HBM-bound
    (197e12, 2 * 819e9, 4.0, 50.0)])  # the larger of the two bounds it
def test_roofline_pct(fl, by, sec, want):
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops.roofline_pct(fl, by, sec, peaks) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v99")
