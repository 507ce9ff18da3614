"""The operation and byte counts against the hand arithmetic written in
PERF.md for fno-sleipner-1chip: N = 64*16*24*88 = 2,162,688 cells, width
W = 40, decoder 128, kept modes K = 48*4*4*10 = 7,680."""
import json
import math
import os

import pytest

from harness.cell import ROOT
from models import fno

N, W, D, K = 2_162_688, 40, 128, 7_680


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs", "fno-sleipner-1chip.json")) as f:
        return json.load(f)


def test_cells_and_modes(cfg):
    assert fno.n_cells(cfg) == N
    assert fno.kept_modes(cfg) == K


def test_forward_flops_by_part(cfg):
    m, nt = 64 * 16 * 24, 88
    fft_block = 2 * W * (m * 2.5 * nt * math.log2(nt) + 45 * 5 * m * math.log2(m))
    hand = {
        "encoder": 2 * 2 * W * N,              # 0.346 GFLOP
        "bypass": 4 * 2 * W * W * N,           # 4 x 6.92 GFLOP
        "fft": 4 * fft_block,                  # 4 x 9.25 GFLOP
        "mix": 4 * 8 * W * W * K,              # 4 x 0.098 GFLOP
        "decoder": 2 * (W * D + D * 1) * N,    # 22.70 GFLOP
    }
    got = fno.forward_flops(cfg)
    for part, value in hand.items():
        assert got[part] == pytest.approx(value, rel=1e-12), part
    assert got["total"] == pytest.approx(88.105e9, rel=1e-4)
    assert fno.train_step_flops(cfg, 1) == pytest.approx(3 * got["total"])


def test_mix_work(cfg):
    ops, nbytes = fno.mix_work(cfg, batch=2)
    assert ops == 8 * W * W * K * 2 == 196_608_000
    assert nbytes == 8 * W * W * K + 2 * 8 * 2 * W * K == 108_134_400
