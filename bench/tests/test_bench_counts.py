"""Operation and byte counts against hand counts at small shapes."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import pytest  # noqa: E402

from harness import counts  # noqa: E402

ENC = {"d_model": 4, "d_ff": 8, "n_layers": 1, "n_heads": 2, "n_kv_heads": 2,
       "gated_mlp": True}


def test_encoder_flops_by_hand():
    # per token: q,k,v 2*4*12 = 96, o 2*4*4 = 32, gated MLP 3 * 2*4*8 = 192
    # causal attention over 3 tokens: 2 * 2 * 4 * (1 + 2 + 3) = 96
    assert counts.encoder_flops(ENC, 3) == 3 * (96 + 32 + 192) + 96


def test_encoder_flops_scale_with_depth_and_plain_mlp():
    two = dict(ENC, n_layers=2)
    assert counts.encoder_flops(two, 5) == 2 * counts.encoder_flops(ENC, 5)
    plain = dict(ENC, gated_mlp=False)
    assert counts.encoder_flops(ENC, 1) - counts.encoder_flops(plain, 1) == 2 * 4 * 8


def test_sbert_row_is_about_half_a_teraflop():
    enc = {"d_model": 768, "d_ff": 3072, "n_layers": 12, "n_heads": 12,
           "n_kv_heads": 12}
    # 12 x 2048 x 18.87 MFLOP of projections and MLP, plus 12 x 2 x 2 x 768
    # x (2048 x 2049 / 2) of causal attention
    assert counts.encoder_flops(enc, 2048) == pytest.approx(
        12 * 2048 * 18_874_368 + 12 * 4 * 768 * 2048 * 2049 / 2)
    assert counts.encoder_flops(enc, 2048) == pytest.approx(5.41e11, rel=0.001)


def test_cobi_counts_by_hand():
    # n=2, one read, one step: 4*4 + 12*2 = 40; readout 2*4 + 3*2 = 14
    assert counts.cobi_flops(2, 1, 1) == 54
    assert counts.cobi_flops(2, 3, 10) == 3 * (10 * 40 + 14)
    # J scaled + J orig (8), h both (4), phases (2), best spins + energy (3)
    assert counts.cobi_bytes(2, 1) == 4 * 17


def test_mcmc_counts_by_hand():
    # n=2, one replica, one sweep: 2 proposals x (2*2 + 10) + initial fields 8
    assert counts.mcmc_flops(2, 1, 1) == 2 * 14 + 8
    assert counts.mcmc_bytes(2, 2) == 4 * (4 + 2 + 4 + 2 + 1)
