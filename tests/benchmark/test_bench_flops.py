"""FLOPs from shapes against hand counts."""

import json
import os

import pytest

from benchmark import flops
from benchmark.manifest import ROOT


def _model(name):
    with open(os.path.join(ROOT, 'benchmark', 'configs', name + '.json')) as f:
        return json.load(f)


def test_geesenet_forward_hand_count():
    # stem 7*11 * 3*3 * 17*32 * 2 = 753,984; a block 7*11*9*32*32*2 =
    # 1,419,264; twelve blocks = 17,031,168
    assert 77 * 9 * 17 * 32 * 2 == 753984
    assert 77 * 9 * 32 * 32 * 2 * 12 == 17031168
    assert flops.forward_flops_per_position(
        _model('geese')['model']) == 17785152


def test_geesenet_lstm_forward_hand_count():
    # stem + 4 blocks + the gate conv over [x, h]: 64 -> 128 channels
    want = 753984 + 4 * 1419264 + 77 * 9 * 64 * 128 * 2
    assert flops.forward_flops_per_position(
        _model('geese_lstm')['model']) == want


@pytest.mark.parametrize('name, steps, burn_in, want', [
    ('geese', 16, 0, 3 * 16 * 17785152),          # 0.85 GFLOP
    ('geese_lstm', 16, 4, (3 * 16 + 4) * 17785152),
])
def test_train_window_flops(name, steps, burn_in, want):
    got = flops.train_window_flops(
        _model(name)['model'],
        {'forward_steps': steps, 'burn_in_steps': burn_in})
    assert got == want
