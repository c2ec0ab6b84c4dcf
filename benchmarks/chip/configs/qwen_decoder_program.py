"""How the benchmark hands a Qwen2 / Qwen3 configuration to the program.

``model_config`` maps the published config keys (and the file's ``run``
settings) onto the program's ``ModelConfig``; ``to_program`` lays the
benchmark's own weights (``qwen_decoder.init``) out as the program's
parameter tree: one period of the layer pattern, stacked over layers, and
the embedding tied to the head.
"""

from __future__ import annotations

import jax.numpy as jnp

# reference weight name -> (block, leaf) in the program's layer group
_LEAVES = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "wq": ("attn", "wq", "kernel"), "bq": ("attn", "wq", "bias"),
    "wk": ("attn", "wk", "kernel"), "bk": ("attn", "wk", "bias"),
    "wv": ("attn", "wv", "kernel"), "bv": ("attn", "wv", "bias"),
    "wo": ("attn", "wo", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
    "w_gate": ("mlp", "gate", "kernel"), "w_up": ("mlp", "up", "kernel"),
    "w_down": ("mlp", "down", "kernel"),
}

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict, n_workers: int):
    """The program's ``ModelConfig`` for this file, with ``n_workers``."""
    from repro.models.config import ModelConfig
    run = cfg["run"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or (cfg["hidden_size"]
                                         // cfg["num_attention_heads"]),
        qkv_bias=bool(cfg.get("attention_bias",
                              cfg["model_type"] == "qwen2")),
        qk_norm=cfg["model_type"] == "qwen3",
        rope_theta=float(cfg["rope_theta"]),
        sliding_window=(cfg["sliding_window"] if cfg.get("use_sliding_window")
                        else None),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=cfg["rms_norm_eps"],
        dtype=DTYPES[cfg["torch_dtype"]],
        scan_layers=run["scan_layers"], remat=run["remat"],
        ce_chunk=run["ce_chunk"], n_workers=n_workers,
        dude_buffer_dtype=DTYPES[run["buffer_dtype"]],
        source=cfg["source"])


def to_program(weights: dict) -> dict:
    """``qwen_decoder.init`` weights -> the program's parameter tree."""
    group: dict = {}
    for name, path in _LEAVES.items():
        if name not in weights["layers"][0]:
            continue
        node = group
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = jnp.stack([w[name] for w in weights["layers"]])
    return {"embed": {"embedding": weights["embed"]},
            "ln_f": {"scale": weights["ln_f"]},
            "stack": {"prefix": [], "groups": [group], "shared_attn": None}}
