"""The FLOP function against a count made by hand at a toy shape."""

import json
import os

from benchmarks.dv3_flops import flops_per_grad_step

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")


def test_flops_agree_with_a_hand_count_at_a_toy_shape():
    s = dict(screen_size=8, image_channels=1, cnn_channels_multiplier=2, dense_units=4, mlp_layers=1,
             recurrent_state_size=4, hidden_size=4, stochastic_size=2, discrete_size=2, actions=3, bins=5,
             sequence_length=2, batch_size=1, horizon=1)
    # one conv stage: 8x8x1 -> 4x4x2, 4x4 kernel: 2 * 16 outputs * 16 taps * 1 * 2
    encoder = 2 * 4 * 4 * 16 * 1 * 2 + 2 * (1 * 4) + 2 * (4 * 4 * 2 + 4) * 4
    # latent = 4 + 4; dense to 2*4*4, then one transposed conv 4x4x2 -> 8x8x1
    decoder = 2 * 8 * 32 + 2 * 4 * 4 * 16 * 2 * 1
    recurrent = 2 * (4 + 3) * 4 + 2 * (4 + 4) * 12
    stochastic = 2 * 4 * 4 + 2 * 4 * 4  # trunk and head, the same for prior and posterior
    reward, cont, actor, critic = 2 * (8 * 4 + 4 * 5), 2 * (8 * 4 + 4), 2 * (8 * 4 + 4 * 3), 2 * (8 * 4 + 4 * 5)
    rows = 2
    world_model = rows * (encoder + recurrent + 2 * stochastic + decoder + reward + cont)
    behaviour = rows * 1 * (recurrent + stochastic) + rows * 2 * (reward + cont + critic) + 3 * rows * 2 * actor + rows * 4 * critic
    assert flops_per_grad_step(s) == 3 * world_model + behaviour


def test_xl_step_is_in_the_range_the_chip_measured():
    with open(os.path.join(BENCH, "configs", "dv3-XL.json")) as f:
        sizes = json.load(f)["sizes"]
    flops = flops_per_grad_step(sizes)
    # PR 22's own count gave 28.6 % of 197 TFLOP/s at 161.1 ms: about 9.1e12
    assert 5e12 < flops < 1.5e13
    assert flops_per_grad_step(sizes, batch=32) == 2 * flops
