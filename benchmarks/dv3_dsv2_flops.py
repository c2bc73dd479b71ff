"""Floating-point operations and bytes that one gradient step of DreamerV3 over
the DeepSeek-V2 sequence core requires, from shapes and the program's counters.

Matrix products and convolutions (two operations to a multiply-add); the
forward pass once and the backward pass twice the forward where a gradient
flows; nothing recomputed, whatever the program rematerialises. The experts
are counted by the token-expert pairs the router really sent to held experts,
attention's scores by the query-key pairs inside an episode's segment (a
masked pair is not required work), imagination forward only, one token at a
time, through the *absorbed* path: that is the path's own arithmetic, and a
program that rebuilt per-head keys would not get more credit.

The kernels' own work, for their roofline shares, is counted so that no
implementation reads over 100 %: only unmasked pairs and positions count, each
input and output once, a row's shared latent cache once a step and layer
however many of the row's streams read it.
"""

from __future__ import annotations

import math

BF16, F32 = 2, 4


def _mlp(n_in: int, width: int, layers: int) -> int:
    return 2 * (n_in * width + (layers - 1) * width * width)


def _expert_layers(s: dict) -> int:
    return s["num_hidden_layers"] - s["first_k_dense_replace"]


def expert_flops_per_pair(s: dict) -> float:
    return 6.0 * s["hidden_size"] * s["moe_intermediate_size"]


def moe_grouped_work(s: dict, grad_steps: float, window_pairs: float, window_hits: float,
                     decode_pairs: float, decode_hits: float) -> tuple:
    """(operations, bytes) of every grouped product of ``grad_steps`` gradient
    steps: as ``dv3_seq_flops.moe_grouped_work`` counts them (a pair's row in
    and out, a hit expert's weights once each way, every held expert's
    gradient written once a step; the one-token steps forward only), over this
    model's expert layers."""
    D = s["hidden_size"]
    expert = 3 * D * s["moe_intermediate_size"]  # one expert's weights
    flops = (3.0 * window_pairs + decode_pairs) * expert_flops_per_pair(s)
    window = window_pairs * D * BF16 * 5 + window_hits * expert * 2 * BF16 \
        + grad_steps * _expert_layers(s) * s["num_experts"] * expert * F32
    decode = decode_pairs * D * BF16 * 2 + decode_hits * expert * BF16
    return flops, window + decode


def mla_window_work(s: dict, attended_pairs: float, tokens: float) -> tuple:
    """(operations, bytes) of the window passes' scores, softmax-weighted sums
    and their transposes: ``attended_pairs`` query-key pairs inside an
    episode's segment, summed over layers (the program's counter), each
    ``2 (d_nope + d_rope + d_v)`` operations a head forward and twice that
    backward; ``tokens`` token-layers, each with its q, k, v and o read or
    written once each way in the compute type."""
    H = s["num_attention_heads"]
    qk, dv = s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"]
    flops = 3.0 * attended_pairs * 2 * (qk + dv) * H
    nbytes = 2.0 * tokens * H * (2 * qk + 2 * dv) * BF16
    return flops, nbytes


def mla_decode_work(s: dict, context_tokens: float, cache_tokens: float, stream_steps: float) -> tuple:
    """(operations, bytes) of the absorbed one-token attention: per attended
    latent position and head ``2 (r + d_rope)`` operations of score and
    ``2 r`` of value; the latent cache read once (``cache_tokens`` positions
    of ``r + d_rope`` numbers: a row's shared cache once a step and layer, a
    stream's own ring once), the absorbed query in and the attended latent out
    a stream, step and layer (``stream_steps`` of them)."""
    H, r, dr = s["num_attention_heads"], s["kv_lora_rank"], s["qk_rope_head_dim"]
    flops = context_tokens * 2.0 * H * ((r + dr) + r)
    nbytes = cache_tokens * (r + dr) * BF16 + stream_steps * H * ((r + dr) + r) * BF16
    return flops, nbytes


def core_flops_per_token(s: dict, context: float, pairs_per_token: float, absorbed: bool = False) -> dict:
    """Forward operations of one token through the whole core, by part.
    ``context``: positions a token attends to. ``absorbed``: the one-token
    path's arithmetic (``W_UK`` and ``W_UV`` applied to the query and the
    attended latent, a head's scores ``r + d_rope`` wide)."""
    D, H, r = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    project = 2 * D * H * (dn + dr) + 2 * D * (r + dr) + 2 * H * dv * D
    if absorbed:
        mla = project + 2 * H * dn * r + 2 * H * r * dv + 2 * H * ((r + dr) + r) * context
    else:
        mla = project + 2 * r * H * (dn + dv) + 2 * H * (dn + dr + dv) * context
    dense = 6 * D * s["intermediate_size"]
    moe = 2 * D * s["router_outputs"] + 6 * D * s["n_shared_experts"] * s["moe_intermediate_size"] \
        + pairs_per_token * expert_flops_per_pair(s)
    return {"mla": s["num_hidden_layers"] * mla, "mlp": s["first_k_dense_replace"] * dense,
            "moe": _expert_layers(s) * moe}


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
    pairs_per_token = even if held_pairs is None else held_pairs / (tokens * _expert_layers(s))
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

    # an episode of 200-400 steps is 400-800 tokens: a token sees a quarter of a window on average
    window = core_flops_per_token(s, T / 2.0, pairs_per_token)
    world_model = T * B * (encoder + posterior + code_embedding + decoder + reward + cont + prior_head) \
        + tokens * sum(window.values())
    streams = B * (2 * T // s["chunk"]) if streams is None else streams
    decode = core_flops_per_token(s, T / 2.0, even, absorbed=True)
    decode_steps = 2 * H + 1 if decode_steps is None else decode_steps
    imagination = streams * (decode_steps * sum(decode.values()) + H * prior_head)
    imagined = streams * (H + 1)
    behaviour = imagination + imagined * (reward + cont + critic) + 3 * imagined * actor + streams * H * 4 * critic
    return float(3 * world_model + behaviour)
