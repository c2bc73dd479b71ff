"""Floating-point operations one DreamerV3 gradient step requires, from shapes.

Matrix products and convolutions only (two operations to a multiply-add); the
forward pass once, the backward pass twice the forward where a gradient
flows, nothing recomputed. Scan bodies are counted times their trip count:
the RSSM over ``T`` and imagination over the horizon. What the program
evaluates twice (the actor over the imagined trajectory, once to sample and
once for the loss) is counted once: required operations, not executed ones.
"""

from __future__ import annotations

import math


def _mlp(n_in: int, width: int, layers: int) -> int:
    return 2 * (n_in * width + (layers - 1) * width * width)


def flops_per_grad_step(s: dict, batch: int = None) -> float:
    """For one device's share: ``batch`` sequences of ``sequence_length``."""
    B = s["batch_size"] if batch is None else batch
    T, H = s["sequence_length"], s["horizon"]
    units, layers = s["dense_units"], s["mlp_layers"]
    rec, hid = s["recurrent_state_size"], s["hidden_size"]
    stoch = s["stochastic_size"] * s["discrete_size"]
    latent = stoch + rec
    act, bins = s["actions"], s["bins"]
    stages = int(math.log2(s["screen_size"])) - 2
    chans = [s["cnn_channels_multiplier"] * 2**i for i in range(stages)]
    base = s["screen_size"] >> stages

    encoder, c_in, side = 0, s["image_channels"], s["screen_size"]
    for c in chans:
        side //= 2
        encoder += 2 * side * side * 16 * c_in * c
        c_in = c
    embed = base * base * chans[-1] + units
    encoder += _mlp(1, units, layers) + 2 * embed * hid  # reward's mlp, embed projection
    decoder, c_in, side = 2 * latent * chans[-1] * base * base, chans[-1], base
    for c in list(reversed(chans[:-1])) + [s["image_channels"]]:
        decoder += 2 * side * side * 16 * c_in * c  # k=4, s=2: four taps reach each output pixel
        side *= 2
        c_in = c
    recurrent = 2 * (stoch + act) * units + 2 * (rec + units) * 3 * rec
    posterior = 2 * rec * hid + 2 * hid * stoch
    prior = 2 * rec * hid + 2 * hid * stoch
    reward = _mlp(latent, units, layers) + 2 * units * bins
    cont = _mlp(latent, units, layers) + 2 * units
    actor = _mlp(latent, units, layers) + 2 * units * act
    critic = _mlp(latent, units, layers) + 2 * units * bins

    rows = T * B
    world_model = rows * (encoder + recurrent + posterior + prior + decoder + reward + cont)
    imagined = rows * (H + 1)
    behaviour = (
        rows * H * (recurrent + prior)  # the rollout, forward only
        + imagined * (reward + cont + critic)  # returns, forward only
        + 3 * imagined * actor  # sampled once, differentiated once
        + rows * H * (3 * critic + critic)  # critic loss, and the target critic forward
    )
    return float(3 * world_model + behaviour)
