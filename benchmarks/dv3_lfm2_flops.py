"""Floating-point operations and bytes that one gradient step of DreamerV3 over
the LFM2-MoE sequence core requires, from shapes and the program's counters.

Matrix products and convolutions (two operations to a multiply-add); the
forward pass once and the backward pass twice the forward where a gradient
flows; nothing recomputed, whatever the program rematerialises. The experts
are counted by the token-expert pairs the router really sent to held experts,
attention's scores by the query-key pairs inside an episode's segment (a
masked pair is not required work), imagination forward only, one token at a
time. The head is the embedding, tied: one product.

The kernels' own work, for their roofline shares, is counted so that no
implementation reads over 100 %: only unmasked pairs count, each input and
output once each way.
"""

from __future__ import annotations

import math

BF16, F32 = 2, 4


def _mlp(n_in: int, width: int, layers: int) -> int:
    return 2 * (n_in * width + (layers - 1) * width * width)


def layers_of(s: dict) -> dict:
    """How many of the built layers are of each kind."""
    kinds = s["layer_types"][: s["num_hidden_layers"]]
    attn = sum(k == "full_attention" for k in kinds)
    dense = min(s["num_dense_layers"], s["num_hidden_layers"])
    return {"attn": attn, "conv": len(kinds) - attn, "mlp": dense, "moe": s["num_hidden_layers"] - dense}


def expert_flops_per_pair(s: dict) -> float:
    return 6.0 * s["hidden_size"] * s["moe_intermediate_size"]


def moe_grouped_work(s: dict, grad_steps: float, window_pairs: float, window_hits: float,
                     decode_pairs: float, decode_hits: float) -> tuple:
    """(operations, bytes) of every grouped product of ``grad_steps`` gradient
    steps: as ``dv3_seq_flops.moe_grouped_work`` counts them (a pair's row in
    and out, a hit expert's weights once each way, every held expert's
    gradient written once a step; the one-token steps forward only), over this
    model's routing layers."""
    D = s["hidden_size"]
    expert = 3 * D * s["moe_intermediate_size"]  # one expert's weights
    flops = (3.0 * window_pairs + decode_pairs) * expert_flops_per_pair(s)
    window = window_pairs * D * BF16 * 5 + window_hits * expert * 2 * BF16 \
        + grad_steps * layers_of(s)["moe"] * s["num_experts"] * expert * F32
    decode = decode_pairs * D * BF16 * 2 + decode_hits * expert * BF16
    return flops, window + decode


def shortconv_work(s: dict, tokens: float) -> tuple:
    """(operations, bytes) of the two gates and the convolution between them
    over ``tokens`` token-layers of the window passes: ``B``, ``u`` and ``C``
    read and the output written once, in the compute type, and as much again
    for their gradients; a multiply for each gate and a multiply-add a tap,
    forward and twice that backward. Bytes bound it."""
    D, K = s["hidden_size"], s["conv_L_cache"]
    flops = 3.0 * tokens * D * (2 + 2 * K)
    nbytes = 2.0 * tokens * 4 * D * BF16
    return flops, nbytes


def gqa_window_work(s: dict, attended_pairs: float, tokens: float) -> tuple:
    """(operations, bytes) of the window passes' scores, softmax-weighted sums
    and their transposes: ``attended_pairs`` query-key pairs inside an
    episode's segment, summed over the attention layers (the program's
    counter), each ``2 (d + d)`` operations a query head forward and twice that
    backward; ``tokens`` token-layers, each with its q and o (all query heads)
    and its k and v (the key-value heads) read or written once each way in the
    compute type."""
    H, Hkv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s["hidden_size"] // H
    flops = 3.0 * attended_pairs * 2 * (hd + hd) * H
    nbytes = 2.0 * tokens * (2 * H + 2 * Hkv) * hd * BF16
    return flops, nbytes


def core_flops_per_token(s: dict, context: float, pairs_per_token: float) -> dict:
    """Forward operations of one token through the whole core, by part.
    ``context``: positions a token attends to in an attention layer."""
    D, H, Hkv, K = s["hidden_size"], s["num_attention_heads"], s["num_key_value_heads"], s["conv_L_cache"]
    hd, n = D // H, layers_of(s)
    conv = 2 * D * 3 * D + 2 * D * D + (2 + 2 * K) * D
    attn = 2 * 2 * D * H * hd + 2 * 2 * D * Hkv * hd + 2 * H * 2 * hd * context
    dense = 6 * D * s["intermediate_size"]
    moe = 2 * D * s["router_outputs"] + pairs_per_token * expert_flops_per_pair(s)
    return {"conv": n["conv"] * conv, "attn": n["attn"] * attn, "mlp": n["mlp"] * dense, "moe": n["moe"] * moe}


def flops_per_grad_step(s: dict, held_pairs: float = None, batch: int = None, streams: float = None,
                        decode_steps: float = None) -> float:
    """``held_pairs``: token-expert pairs routed to held experts in one step's
    window pass, all layers (the program's counter); left out, an even router's.
    ``streams``, ``decode_steps``: imagination starts a step and one-token steps
    a start (the program's counters); left out, a start at every ``chunk``-th
    token of every row and two tokens a horizon step after the start's own. A
    window token attends to half a window on average at the most (episodes end
    inside it: less), an imagined one to half a window and its own steps."""
    B = s["batch_size"] if batch is None else batch
    T, H = s["sequence_length"], s["horizon"]
    D, codes = s["hidden_size"], s["discrete_size"]
    units, layers, bins, act = s["dense_units"], s["mlp_layers"], s["bins"], s["actions"]
    tokens = 2 * T * B
    even = s["num_experts_per_tok"] * s["num_experts"] / s["router_outputs"]
    pairs_per_token = even if held_pairs is None else held_pairs / (tokens * layers_of(s)["moe"])
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
    prior_head = 2 * D * codes  # the tied embedding's rows of the codes

    window = core_flops_per_token(s, T / 2.0, pairs_per_token)
    world_model = T * B * (encoder + posterior + code_embedding + decoder + reward + cont + prior_head) \
        + tokens * sum(window.values())
    streams = B * (2 * T // s["chunk"]) if streams is None else streams
    decode = core_flops_per_token(s, T / 2.0, even)
    decode_steps = 2 * H + 1 if decode_steps is None else decode_steps
    imagination = streams * (decode_steps * sum(decode.values()) + H * prior_head)
    imagined = streams * (H + 1)
    behaviour = imagination + imagined * (reward + cont + critic) + 3 * imagined * actor + streams * H * 4 * critic
    return float(3 * world_model + behaviour)
