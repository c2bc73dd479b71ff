"""Floating-point operations and bytes that one gradient step of DreamerV3 over
the Qwen3-Next sequence core requires, from shapes and the routed-pair counter.

Matrix products, convolutions and the delta rule's own recurrence (two
operations to a multiply-add); the forward pass once and the backward pass
twice the forward where a gradient flows; nothing recomputed, whatever the
program rematerialises. The experts are counted by the token-expert pairs the
router really sent to held experts (the program's counter), not by a capacity.
Imagination is forward only, one token at a time.

The two kernels' own work, for their roofline shares, is counted as the
recurrence's (or the grouped product's) operations and each input and output
once, whatever implements them: a chunked form's intra-chunk products, a
kernel's padding or a re-read of the weights make a share smaller, never
larger than 100 %.
"""

from __future__ import annotations

import math

BF16, F32 = 2, 4


def _mlp(n_in: int, width: int, layers: int) -> int:
    return 2 * (n_in * width + (layers - 1) * width * width)


def delta_rule_flops_per_token(s: dict) -> float:
    """One token through one layer's recurrence, forward: decay of the state,
    ``S^T k``, the rank-one update, ``S^T q``, over the value heads."""
    return 7.0 * s["linear_key_head_dim"] * s["linear_value_head_dim"] * s["linear_num_value_heads"]


def delta_rule_bytes_per_token(s: dict) -> float:
    """q, k, v in and o out, a value head each, in the compute type; g and beta."""
    heads = s["linear_num_value_heads"]
    return heads * (2 * s["linear_key_head_dim"] + 2 * s["linear_value_head_dim"]) * BF16 + 2 * heads * F32


def gdn_scan_work(s: dict, tokens: float) -> tuple:
    """(operations, bytes) of the three delta-rule layers' scans over ``tokens``
    tokens of a window pass, forward and backward (the transpose of the
    recurrence is twice its forward; it reads what the forward read and the
    output's cotangent, and writes three cotangents)."""
    layers = sum(1 for l in range(s["num_hidden_layers"]) if (l + 1) % s["full_attention_interval"])
    return (3.0 * layers * tokens * delta_rule_flops_per_token(s),
            3.0 * layers * tokens * delta_rule_bytes_per_token(s))


def expert_flops_per_pair(s: dict) -> float:
    return 6.0 * s["hidden_size"] * s["moe_intermediate_size"]


def moe_grouped_work(s: dict, grad_steps: float, window_pairs: float, window_hits: float,
                     decode_pairs: float, decode_hits: float) -> tuple:
    """(operations, bytes) of every grouped product of ``grad_steps`` gradient
    steps, the ones whose device time the share divides by: the window passes'
    and imagination's one-token steps'. All four counts are the program's own
    counters, summed over the steps: token-expert pairs routed to held experts,
    and *hits*, the held experts that got at least one pair, counted once a
    layer and pass (a one-token step is a pass of its own: it cannot know the
    next step's tokens, so it reads its experts again).

    Window pass, forward and backward: a pair's row in and out once each way
    (and in once more for the weights' gradient), a hit expert's weights read
    once each way in the compute type, every held expert's gradient written
    once. One-token steps, forward only: a pair's row in and out, a hit
    expert's weights once. An expert no pair reached costs nothing to read, so
    no routing makes the count larger than the work: the share cannot pass
    100 %."""
    D, L = s["hidden_size"], s["num_hidden_layers"]
    expert = 3 * D * s["moe_intermediate_size"]  # one expert's weights
    flops = (3.0 * window_pairs + decode_pairs) * expert_flops_per_pair(s)
    window = window_pairs * D * BF16 * 5 + window_hits * expert * 2 * BF16 \
        + grad_steps * L * s["num_experts"] * expert * F32
    decode = decode_pairs * D * BF16 * 2 + decode_hits * expert * BF16
    return flops, window + decode


def core_flops_per_token(s: dict, context: float, pairs_per_token: float) -> dict:
    """Forward operations of one token through the whole core, by part."""
    D = s["hidden_size"]
    Hk, Hv, dk, dv = s["linear_num_key_heads"], s["linear_num_value_heads"], s["linear_key_head_dim"], s["linear_value_head_dim"]
    Hq, Hkv, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    conv = 2 * Hk * dk + Hv * dv
    gdn = 2 * D * (conv + Hv * dv) + 2 * D * 2 * Hv + 2 * s["linear_conv_kernel_dim"] * conv + 2 * Hv * dv * D \
        + delta_rule_flops_per_token(s)
    attn = 2 * D * Hq * hd * 2 + 2 * 2 * D * Hkv * hd + 2 * Hq * hd * D + 4 * hd * Hq * context
    moe = 2 * D * s["router_outputs"] + 6 * D * s["shared_expert_intermediate_size"] + 2 * D \
        + pairs_per_token * expert_flops_per_pair(s)
    n_attn = sum(1 for l in range(s["num_hidden_layers"]) if (l + 1) % s["full_attention_interval"] == 0)
    n_gdn = s["num_hidden_layers"] - n_attn
    return {"gdn": n_gdn * gdn, "attn": n_attn * attn, "moe": s["num_hidden_layers"] * moe}


def flops_per_grad_step(s: dict, held_pairs: float = None, batch: int = None, streams: float = None,
                        decode_steps: float = None) -> float:
    """``held_pairs``: token-expert pairs routed to held experts in one step's
    window pass, all layers (the program's counter); left out, an even router's.
    ``streams``, ``decode_steps``: imagination starts a step and one-token steps
    a start (the program's counters); left out, a start at every chunk boundary
    of every row and two tokens a horizon step after the start's own."""
    B = s["batch_size"] if batch is None else batch
    T, H = s["sequence_length"], s["horizon"]
    D, codes = s["hidden_size"], s["discrete_size"]
    units, layers, bins, act = s["dense_units"], s["mlp_layers"], s["bins"], s["actions"]
    tokens = 2 * T * B
    even = s["num_experts_per_tok"] * s["num_experts"] / s["router_outputs"]
    pairs_per_token = even if held_pairs is None else held_pairs / (tokens * s["num_hidden_layers"])
    stages = int(math.log2(s["screen_size"])) - 2
    chans = [s["cnn_channels_multiplier"] * 2**i for i in range(stages)]
    base = s["screen_size"] >> stages
    feat = 2 * D

    encoder, c_in, side = 0, s["image_channels"], s["screen_size"]
    for c in chans:
        side //= 2
        encoder += 2 * side * side * 16 * c_in * c
        c_in = c
    posterior = 2 * base * base * chans[-1] * s["posterior_hidden_size"] + 2 * s["posterior_hidden_size"] * codes
    code_embedding = 2 * codes * D
    decoder, c_in, side = 2 * feat * chans[-1] * base * base, chans[-1], base
    for c in list(reversed(chans[:-1])) + [s["image_channels"]]:
        decoder += 2 * side * side * 16 * c_in * c
        side *= 2
        c_in = c
    reward = _mlp(feat, units, layers) + 2 * units * bins
    cont = _mlp(feat, units, layers) + 2 * units
    actor = _mlp(feat, units, layers) + 2 * units * act
    critic = _mlp(feat, units, layers) + 2 * units * bins
    prior_head = 2 * D * codes

    window = core_flops_per_token(s, (2 * T + 1) / 2.0, pairs_per_token)
    world_model = T * B * (encoder + posterior + code_embedding + decoder + reward + cont + prior_head) \
        + tokens * sum(window.values())
    streams = B * (2 * T // s["chunk"]) if streams is None else streams
    decode = core_flops_per_token(s, T, even)  # a start sees half a window of context on average
    decode_steps = 2 * H + 1 if decode_steps is None else decode_steps
    imagination = streams * (decode_steps * sum(decode.values()) + H * prior_head)
    imagined = streams * (H + 1)
    behaviour = imagination + imagined * (reward + cont + critic) + 3 * imagined * actor + streams * H * 4 * critic
    return float(3 * world_model + behaviour)
