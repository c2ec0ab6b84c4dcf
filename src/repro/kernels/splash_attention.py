"""Causal attention with a backward, for training and prefill on the TPU.

A thin wrapper over the Pallas splash-attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a block-sparse
causal forward that skips the blocks above the diagonal, and dq / dkv
backward kernels behind a ``custom_vjp``.  The MXU takes the bf16 q, k and
v the projections produce; the softmax statistics and the accumulation stay
in f32 inside the kernels.

Layout: the model's ``q [B, S, H, hd]``, ``k, v [B, S, K, hd]`` become the
kernel's ``[H, S, hd]`` / ``[K, S, hd]``, vmapped over batch (and whatever
the caller vmaps over, e.g. workers).  The kernel's MHA entry takes K
dividing H: query head ``h`` reads KV head ``h // (H / K)``, as
``attention._expand_kv`` repeats them, and its dkv kernel sums each group's
gradients in its own grid, so OLMoE's MHA and Qwen's GQA take one path.
The kernel applies no scale: q is scaled by ``1/sqrt(hd)`` in f32 and
rounded once to its dtype, the rounding the jnp scan's default-precision
f32 dot applies on the TPU.

The kernel (its mask tables) is built once per (S, H, K, block, interpret),
at trace time, outside any trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as sa

LANES = 128
BLOCK = 512     # q and kv block of every kernel, where S allows it


def block_size(seq_len: int) -> int:
    """The largest of 512, 256, 128 that divides ``seq_len``."""
    return next(b for b in (BLOCK, BLOCK // 2, LANES) if seq_len % b == 0)


@functools.lru_cache(maxsize=None)
def _kernel(seq_len: int, heads: int, block: int, interpret: bool):
    mask = sa.MultiHeadMask([sa.CausalMask((seq_len, seq_len))] * heads)
    sizes = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():     # the mask tables are constants
        return sa.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, *, interpret: bool = False):
    """Causal softmax attention; q ``[B, S, H, hd]``, k / v ``[B, S, K,
    hd]`` with K dividing H and S a multiple of 128.  Returns ``[B, S, H,
    hd]`` in q's dtype."""
    _, S, H, hd = q.shape
    kernel = _kernel(S, H, block_size(S), interpret)
    q = (q.astype(jnp.float32) * (1.0 / hd ** 0.5)).astype(q.dtype)
    heads_major = lambda x: x.transpose(0, 2, 1, 3)     # [B, heads, S, hd]
    out = jax.vmap(kernel)(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)
