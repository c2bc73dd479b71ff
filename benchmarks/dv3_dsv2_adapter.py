"""Hooks for a DreamerV3 run whose sequence core is the DeepSeek-V2 decoder
(``algo.world_model.sequence_model=deepseek_v2``), beside ``dv3_seq_adapter.py``.

Everything the sequence-core family's adapter does stays as it is: the
benchmark's weights in place of the program's, the first gradient steps one to
a dispatch, dropped pairs, the recorded stretch of acting (here the absorbed
one-token path over the latent ring) held to the reference's full, un-absorbed
forward pass of the same tokens, and the comparison with the plain reference.
What differs is which of the configuration's sizes the composed program is held
to: this core's own keys.
"""

from __future__ import annotations

from benchmarks import dv3_adapter, dv3_seq_adapter
from benchmarks.dv3_seq_adapter import SIZE_PATHS, StopWindow, compare_with_reference  # noqa: F401 (run.py reads them here)

#: the core's published keys, as the configuration's file and the program's ``core`` block both name them
CORE_KEYS = (
    "hidden_size", "num_hidden_layers", "first_k_dense_replace", "intermediate_size", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
    "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
    "aux_loss_alpha", "vocab_size", "chunk", "cache_len",
)


class Adapter(dv3_seq_adapter.Adapter):
    def _hold_to_config(self, cfg, actions_dim, observation_space) -> None:
        core = cfg["algo"]["world_model"]["core"]
        share = (int(core["held"]["index"]), int(core["held"]["of"]))
        ran = {name: dv3_adapter._get(cfg, dotted) for name, dotted in SIZE_PATHS.items()}
        ran.update({name: core[name] for name in CORE_KEYS})
        ran.update({
            "rope_scaling": dict(core["rope_scaling"]),
            "router_outputs": int(core["n_routed_experts"]),
            "num_experts": int(core["n_routed_experts"]) // share[1],
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
