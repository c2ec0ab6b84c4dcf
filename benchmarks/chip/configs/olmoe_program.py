"""How the benchmark hands an OLMoE configuration to the program.

``model_config`` maps the published config keys (and the file's ``run``
settings) onto the program's ``ModelConfig``: the router at its published
width (``published.num_experts``), the file's ``num_experts`` as the
experts each layer holds from ``expert_offset`` on, q/k norm over the
whole projection.  ``to_program`` lays the benchmark's own weights
(``olmoe.init``) out as the program's parameter tree: one period of the
layer pattern, stacked over layers, and the untied head as a ``[d, V]``
kernel.
"""

from __future__ import annotations

import jax.numpy as jnp

# reference weight name -> (block, leaf) in the program's layer group
_LEAVES = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "wq": ("attn", "wq", "kernel"), "wk": ("attn", "wk", "kernel"),
    "wv": ("attn", "wv", "kernel"), "wo": ("attn", "wo", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"),
    "k_norm": ("attn", "k_norm", "scale"),
    "router": ("moe", "router", "kernel"),
    "w_gate": ("moe", "wgate"), "w_up": ("moe", "wup"),
    "w_down": ("moe", "wdown"),
}

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict, n_workers: int):
    """The program's ``ModelConfig`` for this file, with ``n_workers``."""
    from repro.models.config import ModelConfig
    run = cfg["run"]
    H = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=H, num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // H,
        qkv_bias=bool(cfg["attention_bias"]), qk_norm="full",
        rope_theta=float(cfg["rope_theta"]),
        block_pattern=("moe",),
        num_experts=cfg["published"]["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        router_aux_loss_coef=float(cfg["router_aux_loss_coef"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=cfg["rms_norm_eps"],
        dtype=DTYPES[cfg["torch_dtype"]],
        scan_layers=run["scan_layers"], remat=run["remat"],
        ce_chunk=run["ce_chunk"], n_workers=n_workers,
        dude_buffer_dtype=DTYPES[run["buffer_dtype"]],
        source=cfg["source"])


def to_program(weights: dict) -> dict:
    """``olmoe.init`` weights -> the program's parameter tree."""
    group: dict = {}
    for name, path in _LEAVES.items():
        node = group
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = jnp.stack([w[name] for w in weights["layers"]])
    return {"embed": {"embedding": weights["embed"]},
            "head": {"kernel": weights["head"].T},
            "ln_f": {"scale": weights["ln_f"]},
            "stack": {"prefix": [], "groups": [group], "shared_attn": None}}
