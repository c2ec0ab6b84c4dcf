"""What an MoE configuration's round step has to do, counted from shapes
and from the program's count of routed pairs.

The experts' work is not a function of the shapes: it is the (token, slot)
pairs that the router sent to the experts this chip holds, which the
program counts each round (``moe_rows_held``, over the workers and
layers).  Everything else is counted per token as ``counts.py`` counts a
dense layer (2·M·N·K per matmul, causal attention at half occupancy,
forward and backward without the recompute that remat adds), with the
router at its published width and the untied head.
"""

from __future__ import annotations

BF16 = 2
# grouped matmuls of one layer's held experts per round, as the program
# runs them: the forward's gate, up and down projections; the remat'd
# forward's gate and up again (the layer's output is not needed there);
# the backward's dy @ w_down^T, the down, gate and up weight gradients and
# the input's two cotangent products
GMMS = 3 + 2 + 6


def _sizes(cfg: dict) -> tuple:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, cfg["num_key_value_heads"], hd, cfg["intermediate_size"]


def dense_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per token of everything but the experts:
    attention projections, the router and the head, and causal attention."""
    d, H, K, hd, _ = _sizes(cfg)
    L, E = cfg["num_hidden_layers"], cfg["published"]["num_experts"]
    weights = L * (d * H * hd + 2 * d * K * hd + H * hd * d + d * E) \
        + d * cfg["vocab_size"]
    attn = L * 2 * 2 * H * hd * seq_len * 0.5
    return 3.0 * (2.0 * weights + attn)


def expert_flops_per_row(cfg: dict) -> float:
    """Forward + backward FLOPs of one (token, slot) pair at a held expert:
    3 × 2 × its three SwiGLU products."""
    d, _, _, _, f = _sizes(cfg)
    return 3.0 * 2.0 * 3 * d * f


def train_flops(cfg: dict, seq_len: int, tokens: float,
                rows_held: float) -> float:
    """Model FLOPs of ``tokens`` trained tokens whose pairs reached held
    experts ``rows_held`` times."""
    return (dense_flops_per_token(cfg, seq_len) * tokens
            + expert_flops_per_row(cfg) * rows_held)


def expert_work(cfg: dict, rows_held: float, n_workers: int) -> tuple:
    """``(FLOPs, HBM bytes)`` of the held experts' grouped matmuls over
    ``rows_held`` routed pairs (all layers), as the program runs them
    (``GMMS`` per layer): each reads its rows and writes its product rows
    (bf16, ``d + f`` wide together), reads the layer's held weights, and
    the three weight gradients write one bf16 copy per worker."""
    d, _, _, _, f = _sizes(cfg)
    L, held = cfg["num_hidden_layers"], cfg["num_experts"]
    w = held * d * f * BF16
    flops = GMMS * 2.0 * d * f * rows_held
    rows = GMMS * (d + f) * BF16 * rows_held
    weights = L * (GMMS * w + 3 * n_workers * w)
    return flops, rows + weights
