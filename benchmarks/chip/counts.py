"""What the algorithm has to do, counted from shapes, and the chip's peaks.

``train_flops_per_token`` follows the program's ``launch/costs.py``
formulas (copied: 2·M·N·K per matmul, causal attention at half
occupancy), for the forward and backward passes, without the recomputed
forward that remat adds.  ``round_bytes`` counts the HBM traffic the DuDe
round must move, whatever implements it.  ``peaks`` reads ``peaks.json``,
keyed by ``device_kind``; a device that is not there is an error.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
F32 = 4


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise LookupError(f"device_kind {device_kind!r} is not in "
                          f"peaks.json ({sorted(table)})")
    return table[device_kind]


def _sizes(cfg: dict) -> tuple:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, cfg["num_key_value_heads"], hd, cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product: attention and MLP projections
    of every layer, and the head (the tied embedding counts once, as the
    head; the lookup is no product)."""
    d, H, K, hd, f = _sizes(cfg)
    layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def param_count(cfg: dict) -> int:
    """All parameters: matmul weights, norm scales and QKV biases."""
    d, H, K, hd, _ = _sizes(cfg)
    per_layer = 2 * d
    if cfg.get("attention_bias", cfg["model_type"] == "qwen2"):
        per_layer += H * hd + 2 * K * hd
    if cfg["model_type"] == "qwen3":
        per_layer += 2 * hd
    return matmul_params(cfg) + cfg["num_hidden_layers"] * per_layer + d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per trained token: 3 × (2 × matmul
    weights + causal attention's 2·2·H·hd·S/2 per layer)."""
    _, H, _, hd, _ = _sizes(cfg)
    attn = cfg["num_hidden_layers"] * 2 * 2 * H * hd * seq_len * 0.5
    return 3.0 * (2.0 * matmul_params(cfg) + attn)


def round_bytes(n: int, P: int, fresh_bytes: int, slab_bytes: int,
                n_slots: int = 0) -> int:
    """HBM bytes of one DuDe round with the optimizer apply: one read of
    the fresh ``[n, P]`` gradients, a read and a write each of the two
    ``[n, P]`` slabs (``g_workers``, ``inflight``), and of the f32 ``[P]``
    params, ``g_bar`` and optimizer slots."""
    return (n * P * fresh_bytes + 2 * 2 * n * P * slab_bytes
            + 2 * (2 + n_slots) * P * F32)


def dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float32": 4}[name]

