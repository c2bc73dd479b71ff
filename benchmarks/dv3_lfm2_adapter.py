"""Hooks for a DreamerV3 run whose sequence core is the LFM2-MoE decoder
(``algo.world_model.sequence_model=lfm2_moe``), beside ``dv3_seq_adapter.py``.

Everything the sequence-core family's adapter does stays as it is: the
benchmark's weights in place of the program's, the first gradient steps one to
a dispatch, dropped pairs, the recorded stretch of acting (here the one-token
path over two gated rows a convolution layer and a key-value ring) held to the
reference's full forward pass of the same tokens. What differs:

- which of the configuration's sizes the composed program is held to: this
  core's own keys;
- **the selection bias**, the one leaf training moves outside the gradient, is
  compared on its own. Each checked step keeps the bias it left and the load
  of every router output it acted on (the step's ``Core/router_load``); the
  reference gives the same. A rounded score that flips a token's choice moves a
  load by one, and ``sign(mean - load)`` turns on that only where a load sits
  at the mean. So an entry (layer, expert) is held **exactly** as long as, in
  every step so far, the reference's load of it lay further from the layer's
  mean than the pairs the two routings disagree on in that layer (half the sum
  of the loads' differences: no expert's load can differ by more):
  ``bias_bad_entries`` counts held entries that differ at any step (limit 0),
  ``bias_entries_left_out`` the entries no longer held after the last step
  (a routing far from the reference's leaves nearly all out, so it has a limit
  too);
- one more fault beside ``dv3_seq_adapter.CONTROLS``: ``no_bias``, the
  reference choosing its experts by the score alone, standing in for a program
  that ignores the bias;
- **``decode_gap`` is the median over the recorded stretch's action positions**,
  not the worst of them as in the other sequence cores' cells. A sigmoid
  router's fourth expert weighs its own score, a quarter of the token's
  experts, so one rounded score that flips who is fourth by ``s + b`` moves
  that position and the two after it (the convolution's rows) by several times
  what rounding moves the rest; a lower precision moves every position. The
  worst position is still read, as ``decode_gap_worst``, and compared with
  nothing (the limits file has both statistics' readings).
"""

from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from benchmarks import dv3_adapter, dv3_seq_adapter
from benchmarks.dv3_seq_adapter import (  # noqa: F401 (run.py reads StopWindow here)
    CHECK_STEPS, SIZE_PATHS, StopWindow, gaps, program_readings, reference_readings,
)

#: the core's published keys, as the configuration's file and the program's ``core`` block both name them
CORE_KEYS = (
    "hidden_size", "num_hidden_layers", "num_dense_layers", "intermediate_size", "conv_L_cache", "num_attention_heads",
    "num_key_value_heads", "rope_theta", "norm_eps", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "use_expert_bias", "bias_update_rate", "vocab_size", "chunk", "cache_len",
)
CONTROLS = {**dv3_seq_adapter.CONTROLS, "no_bias": dict(bias=False)}
BIAS = "expert_bias"


def _biases(flat: dict) -> np.ndarray:
    """The selection biases of a flat parameter dict, a row a routing layer in the stack's order."""
    names = sorted((n for n in flat if n.endswith("/" + BIAS)), key=lambda n: int(n.split("layers_")[1].split("/")[0]))
    return np.stack([np.asarray(flat[n], np.float32) for n in names])


class Adapter(dv3_seq_adapter.Adapter):
    def _hold_to_config(self, cfg, actions_dim, observation_space) -> None:
        core = cfg["algo"]["world_model"]["core"]
        share = (int(core["held"]["index"]), int(core["held"]["of"]))
        ran = {name: dv3_adapter._get(cfg, dotted) for name, dotted in SIZE_PATHS.items()}
        ran.update({name: core[name] for name in CORE_KEYS})
        ran.update({
            "layer_types": list(core["layer_types"]),
            "router_outputs": int(core["num_experts"]),
            "num_experts": int(core["num_experts"]) // share[1],
            "expert_share_index": share[0],
            "actions": tuple(actions_dim)[0] if len(tuple(actions_dim)) == 1 else tuple(actions_dim),
            "image_channels": observation_space["rgb"].shape[0],
        })
        wrong = [(name, self.sizes[name], value) for name, value in ran.items() if value != self.sizes[name]]
        for module in dv3_adapter.MODULES:
            opt = cfg["algo"][module]["optimizer"]
            ran_opt = {"lr": opt["lr"], "eps": opt["eps"], "betas": list(opt["betas"]),
                       "clip": cfg["algo"][module]["clip_gradients"]}
            if ran_opt != self.sizes["optim"][module]:
                wrong.append((f"optim.{module}", self.sizes["optim"][module], ran_opt))
        if wrong:
            raise RuntimeError(f"the run departs from the configuration's file (name, file, run): {wrong}")

    def _record_step(self, state, metrics, data_stack, i, scanned) -> None:
        super()._record_step(state, metrics, data_stack, i, scanned)
        core = dv3_adapter.flat_leaves(state["params"]["world_model"]["core"])
        self.steps[-1]["balance"] = (np.asarray(metrics["Core/router_load"], np.float32), _biases(core))


# -- the comparison with the plain reference ------------------------------------


class Recording:
    """The reference, keeping of every step what its balance step acted on and
    left: ``balance`` is a list of ``(load, bias)``, each ``[routing layers,
    E]``. ``bias=False`` makes it the fault that leaves the bias out of the
    selection."""

    def __init__(self, reference, bias: bool = True):
        self.reference, self.bias, self.balance = reference, bias, []

    def __getattr__(self, name):
        return getattr(self.reference, name)

    def train_step(self, state, batch, key, tau, **kw):
        state, report = self.reference.train_step(state, batch, key, tau, bias=self.bias, **kw)
        self.balance.append((np.asarray(report["router_load"], np.float32), _biases(state["params"])))
        return state, report


def bias_numbers(mine: List[tuple], sound: List[tuple]) -> Dict[str, int]:
    """The selection bias after each checked step against the reference's, over
    the entries held (see the module's docstring)."""
    held, bad = None, 0
    for (load, bias), (ref_load, ref_bias) in zip(mine, sound):
        disagree = 0.5 * np.abs(load - ref_load).sum(-1, keepdims=True)
        far = np.abs(ref_load - ref_load.mean(-1, keepdims=True)) > disagree
        held = far if held is None else held & far
        bad += int((held & (bias != ref_bias)).sum())
    return {"bias_bad_entries": bad, "bias_entries_left_out": int((~held).sum())}


def decode_gaps(reference, config, stretch, params, device, mode="f32", held=True):
    """Acting's one-token prior logits over the recorded stretch against the
    reference's full forward pass of the same tokens, a position's largest
    difference over the reference's largest logit there: the median over the
    stretch's action positions (``decode_gap``) and the worst of them
    (``decode_gap_worst``), and how many positions there were. ``mode`` other
    than ``f32`` (or ``held=False``) reads the control: the reference so altered
    against itself. The stretch is the one ``dv3_seq_adapter.decode_gap`` takes:
    the env that has gone longest since its last reset, from that reset on."""
    import jax
    import jax.numpy as jnp

    sizes = config["sizes"]
    resets = np.stack([row["reset"] for row in stretch])
    since = [(len(stretch) - hits[-1], env, int(hits[-1])) for env in range(resets.shape[1])
             for hits in [np.nonzero(resets[:, env])[0]] if len(hits)]
    if not since:
        return {"decode_gap": None, "decode_gap_worst": None}, 0
    _, env, first = max(since, key=lambda item: (item[0], -item[1]))
    with jax.default_device(device):
        tokens = jnp.stack([row["tokens"][env] for row in stretch[first:]]).reshape(-1).astype(jnp.int32)
        mine = jnp.stack([row["prior_logits"][env] for row in stretch[first:]])
        n = tokens.shape[0]
        pad = -n % sizes["chunk"]
        reset = jnp.zeros((n + pad,), jnp.int32).at[0].set(1)
        flat = dict(dv3_adapter.flat_leaves(params))
        frozen = reference.freeze(sizes)
        sound = reference.core_forward(flat, jnp.pad(tokens, (0, pad)), reset, sizes=frozen)[: n // 2]
        if mode != "f32" or not held:
            mine = reference.core_forward(flat, jnp.pad(tokens, (0, pad)), reset, sizes=frozen, mode=mode, held=held)[: n // 2]
        by_position = np.asarray(jnp.max(jnp.abs(mine - sound), -1) / jnp.max(jnp.abs(sound), -1), np.float64)
    return {"decode_gap": float(np.median(by_position)), "decode_gap_worst": float(by_position.max())}, n // 2


def compare_with_reference(reference, config, steps, seed, chips, device, controls=()) -> dict:
    """``dv3_seq_adapter.compare_with_reference`` with the balance step's
    numbers beside the gaps, for the run and for every control."""
    if len(steps) < CHECK_STEPS:
        raise RuntimeError(f"only {len(steps)} of {CHECK_STEPS} gradient steps were recorded")
    if chips != 1:
        raise RuntimeError("this configuration's cells run on one chip")
    stretch, params = steps[-1].pop("stretch")
    numbers: Dict[str, object] = {"dropped_pairs": steps[-1].pop("dropped_pairs")}
    control_numbers: Dict[str, dict] = {name: {} for name in controls}
    found, compared = decode_gaps(reference, config, stretch, params, device) if stretch else ({"decode_gap": None, "decode_gap_worst": None}, 0)
    numbers.update(found)
    dv3_seq_adapter._note(f"decode check: {compared} action positions of {len(stretch)} recorded steps")
    for name in controls:
        kw = {k: v for k, v in CONTROLS[name].items() if k in ("mode", "held")}
        if kw and stretch:
            control_numbers[name].update(decode_gaps(reference, config, stretch, params, device, **kw)[0])
    del stretch, params
    sound = Recording(reference)
    sound_readings = reference_readings(sound, config, steps, seed, device)
    found = gaps(program_readings(steps), sound_readings)
    print(f"check worst leaves: {found.pop('_worst_leaves')}", file=sys.stderr)
    numbers.update(found)
    numbers.update(bias_numbers([step["balance"] for step in steps], sound.balance))
    for name in controls:
        alter = dict(CONTROLS[name])
        altered = Recording(reference, bias=alter.pop("bias", True))
        readings = gaps(reference_readings(altered, config, steps, seed, device, **alter), sound_readings)
        readings.pop("_worst_leaves", None)
        control_numbers[name].update(readings, **bias_numbers(altered.balance, sound.balance))
    if controls:
        numbers["_controls"] = control_numbers
    return numbers
