"""Analytic operator-level cost model: FLOPs and HBM bytes per (arch, shape).

Why analytic: XLA's ``cost_analysis`` counts ``lax.scan`` bodies once (verified
in tests/test_roofline_model.py), and our stacks scan over layer groups,
attention chunks, and recurrences.  The formulas below follow exact tensor
shapes (the same arithmetic XLA executes); the test suite validates them
against compiled cost_analysis on scan-free reduced configs.

Conventions:
  * FLOPs: 2*M*N*K per matmul; causal attention at 0.5 occupancy.
  * train FLOPs = fwd * (3 + 1 if remat)  (bwd = 2x fwd; remat refwds).
  * HBM bytes are GLOBAL (sum over devices); the roofline divides by chips.
  * DuDe traffic: the paper-faithful masked sweep reads+writes ALL n_workers
    buffers every round — the memory-term tax the §Perf pass attacks.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from .steps import INPUT_SHAPES

F32, BF16 = 4, 2


def _layer_kinds(cfg: ModelConfig) -> list[str]:
    return list(cfg.prefix_layers) + list(cfg.block_pattern) * cfg.n_groups


def _attn_flops(cfg, T, B, S, *, decode_cache: int | None = None) -> float:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    proj = 2 * T * (d * H * hd + 2 * d * K * hd + H * hd * d)
    if decode_cache is not None:
        attn = 2 * 2 * B * H * decode_cache * hd  # qk + av against the cache
    else:
        attn = 2 * 2 * B * H * S * S * hd * 0.5  # causal
    return proj + attn


def _mlp_flops(T, d, f, gated: bool = True) -> float:
    return (6 if gated else 4) * T * d * f  # up (+ gate) + down


def _moe_flops(cfg, T) -> float:
    d, E, k, f = cfg.d_model, cfg.num_experts, cfg.experts_per_tok, cfg.moe_d_ff
    router = 2 * T * d * E
    # drop-free: every (token, slot) pair, of which a layer holding
    # experts_held of E computes its share under even routing
    routed_tokens = T * k * (cfg.experts_held or E) / E
    expert = 6 * routed_tokens * d * f
    shared = 6 * T * d * f * cfg.num_shared_experts
    return router + expert + shared


def _mamba_flops(cfg, T, B, S) -> float:
    from ..models.transformer import mamba_cfg
    m = mamba_cfg(cfg)
    di, N, H, P, Q = m.d_inner, m.d_state, m.num_heads, m.head_dim, m.chunk
    in_p = 2 * T * cfg.d_model * (2 * di + 2 * N + H)
    conv = 4 * T * m.conv_dim * m.conv_width
    Qe = min(Q, S)
    ssd = 2 * B * S * Qe * (N + H * P) + 6 * B * S * H * P * N
    out_p = 2 * T * di * cfg.d_model
    return in_p + conv + ssd + out_p


def _mlstm_flops(cfg, T) -> float:
    from ..models.transformer import mlstm_cfg
    m = mlstm_cfg(cfg)
    di, H, hd = m.d_inner, m.num_heads, m.head_dim
    # block-diagonal qkv: 3 * 2 * T * di * hd (not di^2)
    proj = 2 * T * cfg.d_model * 2 * di + 3 * 2 * T * di * hd + 4 * T * di * H
    cell = 5 * T * H * hd * hd  # outer product + C update + Cq readout
    down = 2 * T * di * cfg.d_model
    return proj + cell + down


def _slstm_flops(cfg, T) -> float:
    from ..models.transformer import slstm_cfg
    s = slstm_cfg(cfg)
    d, hd = cfg.d_model, s.head_dim
    proj = 4 * 2 * T * d * d
    recur = 4 * 2 * T * d * hd
    ff = int(8 * d / 3 / 64) * 64 or 64
    return proj + recur + 6 * T * d * ff / 1.5  # up(2f) + down


def forward_flops(cfg: ModelConfig, shape_name: str) -> dict:
    spec = INPUT_SHAPES[shape_name]
    S, B = spec["seq_len"], spec["global_batch"]
    kind = spec["kind"]
    decode_cache = S if kind == "decode" else None
    S_eff = 1 if kind == "decode" else S
    T = B * S_eff
    per_kind = {
        "attn": lambda: _attn_flops(cfg, T, B, S_eff, decode_cache=decode_cache)
        + _mlp_flops(T, cfg.d_model, cfg.dense_d_ff or cfg.d_ff, cfg.mlp_gated),
        "moe": lambda: _attn_flops(cfg, T, B, S_eff, decode_cache=decode_cache)
        + _moe_flops(cfg, T),
        "mamba": lambda: _mamba_flops(cfg, T, B, S_eff),
        "mamba_shared_attn": lambda: _mamba_flops(cfg, T, B, S_eff)
        + _attn_flops(cfg, T, B, S_eff, decode_cache=decode_cache)
        + _mlp_flops(T, cfg.d_model, cfg.d_ff),
        "mlstm": lambda: _mlstm_flops(cfg, T),
        "slstm": lambda: _slstm_flops(cfg, T),
    }
    total = 0.0
    for k in _layer_kinds(cfg):
        total += per_kind[k]()
    head = 2 * T * cfg.d_model * cfg.vocab_size * max(1, cfg.num_codebooks)
    if cfg.frontend:
        total += 2 * T * cfg.frontend_dim * cfg.d_model  # projector
    return {"layers": total, "head": head, "total": total + head}


def param_counts(cfg: ModelConfig) -> dict:
    """Exact param counts from abstract init (no allocation)."""
    from ..models import lm_init
    shapes = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = emb = expert = 0
    for path, leaf in leaves:
        ps = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        if "embedding" in ps or "/head/" in ps or ps.endswith("head/kernel"):
            emb += n
        if any(w in ps for w in ("wup", "wgate", "wdown")):
            expert += n
    active = total
    if cfg.num_experts:
        active = total - expert * (1 - cfg.experts_per_tok / cfg.num_experts)
    return {"total": total, "embedding": emb, "active": active}


def model_flops_6nd(cfg: ModelConfig, shape_name: str) -> float:
    """6*N*D reference (active params for MoE; D = tokens this step)."""
    spec = INPUT_SHAPES[shape_name]
    S, B = spec["seq_len"], spec["global_batch"]
    kind = spec["kind"]
    tokens = B * (1 if kind == "decode" else S)
    n = param_counts(cfg)["active"]
    mult = 6 if kind == "train" else 2
    return mult * n * tokens


def hbm_bytes(cfg: ModelConfig, shape_name: str, n_workers: int | None = None,
              buffer_bytes: int = BF16, *, dude_sweep: bool = True) -> dict:
    """Global HBM traffic per step (dominant terms)."""
    spec = INPUT_SHAPES[shape_name]
    S, B = spec["seq_len"], spec["global_batch"]
    kind = spec["kind"]
    n = n_workers or cfg.n_workers
    P = param_counts(cfg)["total"]
    big = cfg.name in ("qwen1.5-110b", "kimi-k2-1t-a32b")
    pbytes = BF16 if big else F32

    out: dict[str, float] = {}
    if kind == "train":
        T = B * S
        # params: fwd read + bwd read (+ remat refwd read); grads written [n,...]
        reads = 3 if cfg.remat else 2
        out["params"] = reads * P * pbytes + n * P * pbytes
        if dude_sweep:
            # paper-faithful masked sweep: r+w of both stacked buffers
            out["dude"] = 2 * 2 * n * P * buffer_bytes + 2 * P * F32 + 2 * P * pbytes
        else:
            # §Perf indexed commit: touch only committing workers (~1/tau_avg)
            out["dude"] = 2 * 2 * P * buffer_bytes + 2 * P * F32 + 2 * P * pbytes
        # attention score tiles (XLA chunked path materializes [B,H,S,chunk]
        # per step; total S^2 across chunks, fwd + bwd + remat refwd)
        att_heads = sum(
            1 for k in _layer_kinds(cfg)
            if k in ("attn", "moe", "mamba_shared_attn")
        )
        out["attn_scores"] = 3 * att_heads * B * cfg.num_heads * S * S * F32 * 0.5
        out["activations"] = 12 * len(_layer_kinds(cfg)) * T * cfg.d_model * BF16
    else:
        out["params"] = P * pbytes
        if kind == "prefill":
            att_heads = sum(
                1 for k in _layer_kinds(cfg)
                if k in ("attn", "moe", "mamba_shared_attn")
            )
            out["attn_scores"] = att_heads * B * cfg.num_heads * S * S * F32 * 0.5
            out["kv_write"] = att_heads * 2 * B * S * cfg.num_kv_heads * cfg.hd * BF16
            out["activations"] = 8 * len(_layer_kinds(cfg)) * B * S * cfg.d_model * BF16
        else:  # decode: read the whole cache (baseline reads full window)
            att_heads = sum(
                1 for k in _layer_kinds(cfg)
                if k in ("attn", "moe", "mamba_shared_attn")
            )
            out["kv_read"] = att_heads * 2 * B * S * cfg.num_kv_heads * cfg.hd * BF16
            ssm_layers = sum(
                1 for k in _layer_kinds(cfg)
                if k in ("mamba", "mamba_shared_attn", "mlstm", "slstm")
            )
            if ssm_layers:
                from ..models.transformer import mamba_cfg, mlstm_cfg
                st = 0
                for k in _layer_kinds(cfg):
                    if k.startswith("mamba"):
                        m = mamba_cfg(cfg)
                        st += B * m.num_heads * m.head_dim * m.d_state * F32
                    elif k == "mlstm":
                        m = mlstm_cfg(cfg)
                        st += B * m.num_heads * m.head_dim ** 2 * F32
                    elif k == "slstm":
                        st += 3 * B * cfg.d_model * F32
                out["ssm_state"] = 2 * st
    out["total"] = float(sum(out.values()))
    return out


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    arch: str
    shape: str
    chips: int
    flops: float
    hbm: float
    collective: float
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops: float
    useful_ratio: float

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)


def roofline(cfg: ModelConfig, shape_name: str, chips: int,
             collective_bytes: float, hw: dict,
             n_workers: int | None = None, *, dude_sweep: bool = True) -> RooflineTerms:
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    fwd = forward_flops(cfg, shape_name)["total"]
    mult = (3 + (1 if cfg.remat else 0)) if kind == "train" else 1
    flops = fwd * mult
    hb = hbm_bytes(cfg, shape_name, n_workers, dude_sweep=dude_sweep)["total"]
    mf = model_flops_6nd(cfg, shape_name)
    return RooflineTerms(
        arch=cfg.name, shape=shape_name, chips=chips,
        flops=flops, hbm=hb, collective=collective_bytes,
        t_compute=flops / (chips * hw["peak_flops_bf16"]),
        t_memory=hb / (chips * hw["hbm_bw"]),
        t_collective=collective_bytes / (chips * hw["ici_bw"]),
        model_flops=mf,
        useful_ratio=mf / max(flops, 1.0),
    )
