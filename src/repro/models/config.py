"""Model configuration shared by model code and the per-arch config files."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None      # default d_model // num_heads

    # attention options
    qkv_bias: bool = False
    qk_norm: bool | str = False         # True: per head (Qwen3); "full": over
                                        # the whole q / k projection (OLMoE)
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None  # enables long_500k for dense archs
    attn_chunk: int = 512

    # block pattern: one *period* of layer kinds, cycled num_layers/period times
    # kinds: attn | moe | mamba | mamba_shared_attn | mlstm | slstm
    block_pattern: Tuple[str, ...] = ("attn",)
    # layers prepended before the periodic stack (e.g. kimi's dense layer 0)
    prefix_layers: Tuple[str, ...] = ()

    # moe
    num_experts: int = 0                # the router's width
    experts_per_tok: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    experts_held: int = 0               # experts each MoE layer holds, from
    expert_offset: int = 0              # expert_offset on (0: all of them)
    norm_topk_prob: bool = True         # renormalise the top-k gates
    router_aux_loss_coef: float = 0.01  # x the load-balancing loss
    dense_d_ff: int = 0                 # d_ff for 'attn' layers in MoE models
    mlp_gated: bool = True              # SwiGLU (False: GELU 2-matrix MLP)

    # ssm
    ssm_state: int = 64

    # frontend stub (vlm / audio): precomputed embeddings prepended to tokens
    frontend: Optional[str] = None      # vision | audio
    frontend_dim: int = 0
    num_prefix_tokens: int = 0
    num_codebooks: int = 1              # musicgen: 4 EnCodec codebooks

    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    # runtime knobs
    scan_layers: bool = True
    remat: bool = True
    # §Perf: compute the LM head + cross-entropy in ceil(S / ce_chunk)
    # vocabulary slices inside a checkpointed scan, each slice's logits as
    # large as a ce_chunk-token chunk of [T, V], which is never materialized;
    # the head's slices are the scan's xs, so its gradient is written once
    # per slice (0 = off; S <= ce_chunk is the unchunked loss).
    ce_chunk: int = 0

    # DuDe / distribution defaults for this arch (overridable at launch)
    n_workers: int = 16
    dude_buffer_dtype: Any = jnp.bfloat16

    # citation for the assigned-architecture pool
    source: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_groups(self) -> int:
        n = self.num_layers - len(self.prefix_layers)
        assert n % self.period == 0, (
            f"{self.name}: {n} periodic layers not divisible by period {self.period}"
        )
        return n // self.period

    def supports_long_decode(self) -> bool:
        """Sub-quadratic decode: SSM/hybrid natively; attention via SWA."""
        if any(k in ("mamba", "mamba_shared_attn", "mlstm", "slstm")
               for k in self.block_pattern):
            return True
        return self.sliding_window is not None

    def smoke(self) -> "ModelConfig":
        """Reduced variant of the same family for CPU smoke tests."""
        period = len(self.block_pattern)
        return dataclasses.replace(
            self,
            num_layers=max(2, period) + len(self.prefix_layers),
            d_model=256,
            num_heads=4,
            num_kv_heads=max(1, min(self.num_kv_heads, 2)),
            head_dim=64,
            d_ff=512,
            dense_d_ff=512 if self.dense_d_ff else 0,
            vocab_size=512,
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_tok=min(self.experts_per_tok, 2) if self.experts_per_tok else 0,
            experts_held=0,
            expert_offset=0,
            moe_d_ff=128 if self.moe_d_ff else 0,
            ssm_state=16,
            sliding_window=64 if self.sliding_window else None,
            attn_chunk=32,
            num_prefix_tokens=8 if self.num_prefix_tokens else 0,
            frontend_dim=32 if self.frontend_dim else 0,
            dtype=jnp.float32,
            scan_layers=True,
            remat=False,
            n_workers=4,
        )
