"""Grouped-query attention with RoPE, qk-norm (per head, or over the whole
projection), QKV bias, sliding window, KV caches, and memory-bounded chunked
softmax.

Four execution paths:
  * ``attention_ref``      — naive O(S^2) materialized scores (tests/oracles).
  * ``causal_attention``   — (``kernels/splash_attention.py``) the Pallas
                             splash kernels: a causal block-sparse forward
                             and dq / dkv backward kernels on bf16 operands.
                             The training / prefill path on the TPU.
  * ``attention_chunked``  — online-softmax over KV chunks via ``lax.scan``;
                             mathematically identical, O(S * chunk) memory.
                             The training / prefill path everywhere else.
  * ``decode_attend``      — single-token attention against a cache
                             (flash-decode math; optional sliding window).

``attention_apply`` picks the kernel from what it can observe
(:func:`uses_flash_kernel`): the backend is the TPU, no KV cache and no
sliding window is in play, the activations are on no mesh of more than
one device (the shard hook's ``mesh``: a custom call is not partitioned),
and S is a multiple of 128.  The attention core of every path runs under
the ``dude.attention`` device scope.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..kernels.splash_attention import LANES, causal_attention
from ..runtime import spans
from .layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init

Pytree = Any
ShardHook = Callable[[jnp.ndarray, str], jnp.ndarray]
_id_hook: ShardHook = lambda x, name: x

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool | str = False  # True: per head; "full": the whole q / k projection
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None
    chunk: int = 512


def attention_init(key, cfg: AttnConfig) -> Pytree:
    kq, kk, kv, ko = jax.random.split(key, 4)
    H, K, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": dense_init(kq, d, H * hd, bias=cfg.qkv_bias),
        "wk": dense_init(kk, d, K * hd, bias=cfg.qkv_bias),
        "wv": dense_init(kv, d, K * hd, bias=cfg.qkv_bias),
        "wo": dense_init(ko, H * hd, d),
    }
    if cfg.qk_norm == "full":
        p["q_norm"] = rmsnorm_init(H * hd)
        p["k_norm"] = rmsnorm_init(K * hd)
    elif cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _project_qkv(p, x, positions, cfg: AttnConfig, shard: ShardHook):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    full = cfg.qk_norm == "full"
    q = dense(p["wq"], x)
    if full:
        q = rmsnorm(p["q_norm"], q)
    q = q.reshape(B, S, H, hd)
    k = dense(p["wk"], x)
    if full:
        k = rmsnorm(p["k_norm"], k)
    k = k.reshape(B, S, K, hd)
    v = dense(p["wv"], x).reshape(B, S, K, hd)
    if cfg.qk_norm and not full:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "act_heads")
    k = shard(k, "act_kv")
    v = shard(v, "act_kv")
    return q, k, v


def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """[B, S, K, hd] -> [B, S, K*groups, hd] by repetition (GQA)."""
    return jnp.repeat(k, groups, axis=2)


# --------------------------------------------------------------- naive oracle

def attention_ref(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                  q_offset: int = 0):
    """q [B,Sq,H,hd], k/v [B,Sk,K,hd]. Materializes full scores (tests only)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    kx = _expand_kv(k, H // K)
    vx = _expand_kv(v, H // K)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kx.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(hd))
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vx.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------------ chunked online softmax

def attention_chunked(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None, chunk: int = 512):
    """Flash-style online softmax over KV chunks (pure jnp + lax.scan).

    Memory is O(Sq * chunk) per step instead of O(Sq * Sk).  Exactly equal to
    ``attention_ref`` up to float associativity.
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    groups = H // K
    q32 = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Sq)[:, None]

    def step(carry, inp):
        m, l, acc = carry  # [B,H,Sq], [B,H,Sq], [B,Sq,H,hd]
        ci, kci, vci = inp
        kx = _expand_kv(kci, groups).astype(jnp.float32)  # [B,chunk,H,hd]
        vx = _expand_kv(vci, groups).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kx)  # [B,H,Sq,chunk]
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = kpos <= (Sk - 1)  # padding mask
        mask = jnp.broadcast_to(mask, (Sq, chunk))
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pweights = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(pweights, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", pweights, vx)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    # checkpoint the body: the backward recomputes score tiles per chunk
    # instead of saving [n_chunks, B, H, Sq, chunk] — flash-attention-style
    # O(Sq * chunk) memory in both passes.
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------------------------- KV cache

def init_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> Pytree:
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }


def update_cache(cache: Pytree, k: jnp.ndarray, v: jnp.ndarray, index) -> Pytree:
    """Write [B, S_new, K, hd] at position ``index`` (traced scalar ok).

    Single-token decode uses a position-mask ``where`` instead of
    dynamic_update_slice: with the cache sequence-sharded over the ``model``
    axis, a dynamic-index update forces GSPMD into 'involuntary full
    rematerialization' (the whole cache replicated per device — measured at
    1.4 TB/device for qwen3 decode_32k, EXPERIMENTS §Perf iteration D1).
    The mask form is elementwise, so every shard updates locally.
    """
    if k.shape[1] == 1:
        pos = jnp.arange(cache["k"].shape[1])
        hit = (pos == index)[None, :, None, None]
        k_new = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
        v_new = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        return {"k": k_new, "v": v_new}
    k_new = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                         (0, index, 0, 0))
    v_new = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                         (0, index, 0, 0))
    return {"k": k_new, "v": v_new}


def decode_attend(q, cache, length, *, window: Optional[int] = None):
    """Single(-few)-token attention against the cache.

    q: [B, 1, H, hd]; cache k/v: [B, Smax, K, hd]; ``length`` = #valid
    positions (the new token's position is length-1 after the cache update).
    Sliding window masks keys <= length-1-window.  Reads the full cache and
    masks — the Pallas flash_decode kernel and the window-slice optimization
    in §Perf avoid the wasted reads.
    """
    B, Sq, H, hd = q.shape
    K = cache["k"].shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    # Grouped einsum: never materializes the GQA-expanded or upcast cache.
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, cache["k"],
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(jnp.float32(hd))
    kpos = jnp.arange(cache["k"].shape[1])[None, :]
    qpos = (length - Sq) + jnp.arange(Sq)[:, None]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", w.astype(cache["v"].dtype), cache["v"],
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# ----------------------------------------------------------- full attn module

def uses_flash_kernel(*, backend: str, cached: bool, window: Optional[int],
                      sharded: bool, seq_len: int) -> bool:
    """Whether attention runs the Pallas kernel rather than the jnp scan."""
    return (backend == "tpu" and not cached and window is None
            and not sharded and seq_len % LANES == 0)


def attention_apply(
    p: Pytree,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: AttnConfig,
    *,
    cache: Optional[Pytree] = None,
    cache_index=None,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """Self-attention block body.  Returns (out, new_cache)."""
    window = cfg.sliding_window if use_window else None
    q, k, v = _project_qkv(p, x, positions, cfg, shard)
    B, S = x.shape[:2]
    mesh = getattr(shard, "mesh", None)
    flash = uses_flash_kernel(backend=jax.default_backend(),
                              cached=cache is not None, window=window,
                              sharded=mesh is not None and mesh.size > 1,
                              seq_len=S)
    new_cache = None
    if cache is not None:
        new_cache = update_cache(cache, k, v, cache_index)
    with jax.named_scope(spans.ATTENTION):
        if flash:
            out = causal_attention(q, k, v)
        elif cache is None:
            out = attention_chunked(q, k, v, causal=True, window=window,
                                    chunk=cfg.chunk)
        else:
            out = decode_attend(q, new_cache, cache_index + S, window=window)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    out = dense(p["wo"], out)
    return shard(out, "act_resid"), new_cache
