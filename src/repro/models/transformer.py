"""Block assembly: dense / MoE / SSM / hybrid layer kinds, composed in a
periodic pattern and executed with ``lax.scan`` over period groups.

Scanning over *groups* (one period of heterogeneous layers per group) keeps
the HLO size O(period) instead of O(num_layers) — required to compile 80-layer
configs on the CPU-hosted dry-run — while supporting mixed-kind stacks like
zamba2 (5×mamba + 1×mamba+shared-attention per period) and xLSTM (7×mLSTM +
1×sLSTM per period).  Weights for shared blocks (zamba2's attention) are
closure constants, not scanned.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .attention import (
    AttnConfig,
    attention_apply,
    attention_init,
    init_cache as attn_init_cache,
)
from .config import ModelConfig
from .layers import mlp, mlp_init, rmsnorm, rmsnorm_init
from .moe import MoEConfig, moe_apply, moe_init, moe_stats_zero
from .ssm import (
    Mamba2Config, MLSTMConfig, SLSTMConfig,
    mamba2_apply, mamba2_init, mamba2_init_state,
    mlstm_apply, mlstm_init, mlstm_init_state,
    slstm_apply, slstm_init, slstm_init_state,
)

Pytree = Any
ShardHook = Callable[[jnp.ndarray, str], jnp.ndarray]
_id_hook: ShardHook = lambda x, name: x


def attn_cfg(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
        qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, sliding_window=cfg.sliding_window,
        chunk=cfg.attn_chunk,
    )


def moe_cfg(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(
        d_model=cfg.d_model, num_experts=cfg.num_experts,
        experts_per_tok=cfg.experts_per_tok, d_ff=cfg.moe_d_ff,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        norm_topk_prob=cfg.norm_topk_prob,
        num_shared_experts=cfg.num_shared_experts,
    )


def mamba_cfg(cfg: ModelConfig) -> Mamba2Config:
    return Mamba2Config(d_model=cfg.d_model, d_state=cfg.ssm_state)


def mlstm_cfg(cfg: ModelConfig) -> MLSTMConfig:
    return MLSTMConfig(d_model=cfg.d_model, num_heads=cfg.num_heads)


def slstm_cfg(cfg: ModelConfig) -> SLSTMConfig:
    return SLSTMConfig(d_model=cfg.d_model, num_heads=cfg.num_heads)


# ------------------------------------------------------------------ one block

def block_init(key, kind: str, cfg: ModelConfig) -> Pytree:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == "attn":
        d_ff = cfg.dense_d_ff or cfg.d_ff
        return {
            "ln1": rmsnorm_init(d), "attn": attention_init(k1, attn_cfg(cfg)),
            "ln2": rmsnorm_init(d),
            "mlp": mlp_init(k2, d, d_ff, gated=cfg.mlp_gated),
        }
    if kind == "moe":
        return {
            "ln1": rmsnorm_init(d), "attn": attention_init(k1, attn_cfg(cfg)),
            "ln2": rmsnorm_init(d), "moe": moe_init(k2, moe_cfg(cfg)),
        }
    if kind == "mamba":
        return {"ln1": rmsnorm_init(d), "mamba": mamba2_init(k1, mamba_cfg(cfg))}
    if kind == "mamba_shared_attn":
        # shared attention/MLP weights are NOT here (passed separately, reused
        # at every occurrence — zamba2's shared transformer block); this block
        # owns only its mamba and norms.
        return {
            "ln1": rmsnorm_init(d), "mamba": mamba2_init(k1, mamba_cfg(cfg)),
            "ln2": rmsnorm_init(d), "ln3": rmsnorm_init(d),
        }
    if kind == "mlstm":
        return {"ln1": rmsnorm_init(d), "mlstm": mlstm_init(k1, mlstm_cfg(cfg))}
    if kind == "slstm":
        return {"ln1": rmsnorm_init(d), "slstm": slstm_init(k1, slstm_cfg(cfg))}
    raise ValueError(f"unknown block kind {kind!r}")


def make_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Pytree:
    if kind in ("attn", "moe"):
        return attn_init_cache(batch, max_len, cfg.num_kv_heads, cfg.hd, dtype)
    if kind == "mamba":
        return mamba2_init_state(batch, mamba_cfg(cfg), jnp.float32)
    if kind == "mamba_shared_attn":
        return {
            "mamba": mamba2_init_state(batch, mamba_cfg(cfg), jnp.float32),
            "attn": attn_init_cache(batch, max_len, cfg.num_kv_heads, cfg.hd, dtype),
        }
    if kind == "mlstm":
        return mlstm_init_state(batch, mlstm_cfg(cfg), jnp.float32)
    if kind == "slstm":
        return slstm_init_state(batch, slstm_cfg(cfg), jnp.float32)
    raise ValueError(kind)


def block_apply(
    params: Pytree,
    kind: str,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    *,
    shared_attn: Optional[Pytree] = None,
    cache: Optional[Pytree] = None,
    cache_index=None,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """Residual block.  Returns (x, new_cache, aux): aux is an MoE layer's
    router statistics (``moe.moe_stats_zero``), a zero scalar otherwise."""
    acfg = attn_cfg(cfg)
    if kind in ("attn", "moe"):
        h, new_cache = attention_apply(
            params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps), positions,
            acfg, cache=cache, cache_index=cache_index, shard=shard,
            use_window=use_window,
        )
        x = x + h
        if kind == "attn":
            x = x + mlp(params["mlp"], rmsnorm(params["ln2"], x, cfg.norm_eps))
            return x, new_cache, jnp.zeros((), jnp.float32)
        y, stats = moe_apply(params["moe"],
                             rmsnorm(params["ln2"], x, cfg.norm_eps),
                             moe_cfg(cfg))
        return x + y, new_cache, stats

    zero_aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h, st = mamba2_apply(params["mamba"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                             mamba_cfg(cfg), init_state=cache)
        return x + h, st, zero_aux
    if kind == "mamba_shared_attn":
        mcache = cache["mamba"] if cache is not None else None
        acache = cache["attn"] if cache is not None else None
        h, mst = mamba2_apply(params["mamba"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                              mamba_cfg(cfg), init_state=mcache)
        x = x + h
        h2, ast = attention_apply(
            shared_attn["attn"], rmsnorm(params["ln2"], x, cfg.norm_eps), positions,
            acfg, cache=acache, cache_index=cache_index, shard=shard,
            use_window=use_window,
        )
        x = x + h2
        x = x + mlp(shared_attn["mlp"], rmsnorm(params["ln3"], x, cfg.norm_eps))
        new_cache = {"mamba": mst, "attn": ast} if cache is not None else None
        return x, new_cache, zero_aux
    if kind == "mlstm":
        h, st = mlstm_apply(params["mlstm"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                            mlstm_cfg(cfg), init_state=cache)
        return x + h, st, zero_aux
    if kind == "slstm":
        h, st = slstm_apply(params["slstm"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                            slstm_cfg(cfg), init_state=cache)
        return x + h, st, zero_aux
    raise ValueError(kind)


# ------------------------------------------------------------------ the stack

def _aux_zero(cfg: ModelConfig):
    if "moe" in cfg.block_pattern or "moe" in cfg.prefix_layers:
        return moe_stats_zero(cfg.num_experts)
    return jnp.zeros((), jnp.float32)


def _add_aux(total, aux):
    """Layers' aux summed: router statistics where the stack has MoE layers
    (its other layers add nothing), zero scalars otherwise."""
    if isinstance(total, dict):
        return jax.tree.map(jnp.add, total, aux) if isinstance(aux, dict) \
            else total
    return total + aux


def stack_init(key, cfg: ModelConfig) -> Pytree:
    """Stacked parameters: for each period position, leaves have a leading
    [n_groups] dim; prefix layers and shared blocks are unstacked."""
    params: dict = {"prefix": [], "groups": [], "shared_attn": None}
    keys = jax.random.split(key, 2 + len(cfg.prefix_layers) + cfg.period)
    ki = 0
    for kind in cfg.prefix_layers:
        params["prefix"].append(block_init(keys[ki], kind, cfg))
        ki += 1
    for pi, kind in enumerate(cfg.block_pattern):
        gkeys = jax.random.split(keys[ki], cfg.n_groups)
        stacked = jax.vmap(lambda k: block_init(k, kind, cfg))(gkeys)
        params["groups"].append(stacked)
        ki += 1
    if "mamba_shared_attn" in cfg.block_pattern:
        ka, km = jax.random.split(keys[ki])
        params["shared_attn"] = {
            "attn": attention_init(ka, attn_cfg(cfg)),
            "mlp": mlp_init(km, cfg.d_model, cfg.d_ff),
        }
    return params


def stack_caches(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16) -> Pytree:
    """Cache pytree matching stack_init's structure."""
    caches: dict = {"prefix": [], "groups": []}
    for kind in cfg.prefix_layers:
        caches["prefix"].append(make_block_cache(kind, cfg, batch, max_len, dtype))
    for kind in cfg.block_pattern:
        one = make_block_cache(kind, cfg, batch, max_len, dtype)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape), one
        )
        caches["groups"].append(stacked)
    return caches


def stack_apply(
    params: Pytree,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    *,
    caches: Optional[Pytree] = None,
    cache_index=None,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """Run the full layer stack.  Returns (x, new_caches, aux): the router
    statistics summed over the MoE layers where the stack has any, else a
    zero scalar."""
    aux_total = _aux_zero(cfg)
    new_prefix = []
    for i, kind in enumerate(cfg.prefix_layers):
        c = caches["prefix"][i] if caches is not None else None
        x, nc, aux = block_apply(
            params["prefix"][i], kind, x, positions, cfg,
            shared_attn=params["shared_attn"], cache=c, cache_index=cache_index,
            shard=shard, use_window=use_window,
        )
        new_prefix.append(nc)
        aux_total = _add_aux(aux_total, aux)

    shared = params["shared_attn"]

    def group_fn(x, group_params, group_caches):
        aux_g = _aux_zero(cfg)
        new_caches = []
        for pi, kind in enumerate(cfg.block_pattern):
            c = group_caches[pi] if group_caches is not None else None
            x, nc, aux = block_apply(
                group_params[pi], kind, x, positions, cfg,
                shared_attn=shared, cache=c, cache_index=cache_index,
                shard=shard, use_window=use_window,
            )
            new_caches.append(nc)
            aux_g = _add_aux(aux_g, aux)
        return x, new_caches, aux_g

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn, static_argnums=())

    if cfg.scan_layers:
        if caches is None:
            def scan_body_nc(carry, gp):
                xc, aux = carry
                xc, _, aux_g = group_fn(xc, gp, None)
                return (xc, _add_aux(aux, aux_g)), None

            (x, aux_total), _ = jax.lax.scan(
                scan_body_nc, (x, aux_total), params["groups"]
            )
            new_groups = None
        else:
            def scan_body(carry, scanned):
                xc, aux = carry
                gp, gc = scanned
                xc, nc, aux_g = group_fn(xc, gp, gc)
                return (xc, _add_aux(aux, aux_g)), nc

            (x, aux_total), new_groups = jax.lax.scan(
                scan_body, (x, aux_total), (params["groups"], caches["groups"])
            )
    else:
        new_groups = [] if caches is not None else None
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gc = (
                jax.tree.map(lambda a: a[g], caches["groups"])
                if caches is not None else None
            )
            x, nc, aux_g = group_fn(x, gp, gc)
            aux_total = _add_aux(aux_total, aux_g)
            if caches is not None:
                new_groups.append(nc)
        if caches is not None:
            new_groups = jax.tree.map(lambda *xs: jnp.stack(xs), *new_groups)

    new_caches = (
        {"prefix": new_prefix, "groups": new_groups} if caches is not None else None
    )
    return x, new_caches, aux_total
