"""Language-model wrapper: embeddings, layer stack, head, loss, and the three
entry points the launcher lowers (train forward, prefill, decode step).

Batch dict convention (all entry points):
  tokens      [B, S_text]            int32  (musicgen: [B, S_text, n_codebooks])
  labels      [B, S_total]           int32, -1 = masked (train only)
  prefix_emb  [B, P, frontend_dim]   float  (vlm/audio only; stub output)

For frontend archs the effective sequence is [prefix_emb ; tokens] with total
length P + S_text; positions are absolute over the total sequence.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense, dense_init, embed, embedding_init, rmsnorm, rmsnorm_init
from .moe import load_balancing_loss
from .transformer import stack_apply, stack_caches, stack_init

Pytree = Any
ShardHook = Callable[[jnp.ndarray, str], jnp.ndarray]
_id_hook: ShardHook = lambda x, name: x


def lm_init(key, cfg: ModelConfig) -> Pytree:
    k_emb, k_stack, k_head, k_proj = jax.random.split(key, 4)
    params: dict = {"stack": stack_init(k_stack, cfg), "ln_f": rmsnorm_init(cfg.d_model)}
    if cfg.num_codebooks > 1:
        keys = jax.random.split(k_emb, cfg.num_codebooks)
        params["embed"] = [embedding_init(k, cfg.vocab_size, cfg.d_model) for k in keys]
        hkeys = jax.random.split(k_head, cfg.num_codebooks)
        params["head"] = [dense_init(k, cfg.d_model, cfg.vocab_size, scale=0.02)
                          for k in hkeys]
    else:
        params["embed"] = embedding_init(k_emb, cfg.vocab_size, cfg.d_model)
        if not cfg.tie_embeddings:
            params["head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, scale=0.02)
    if cfg.frontend:
        params["frontend_proj"] = dense_init(k_proj, cfg.frontend_dim, cfg.d_model)
    return params


def _embed_tokens(params, tokens, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.num_codebooks > 1:
        parts = [embed(params["embed"][c], tokens[..., c], cfg.dtype)
                 for c in range(cfg.num_codebooks)]
        return sum(parts)
    return embed(params["embed"], tokens, cfg.dtype)


def _head(params, x, cfg: ModelConfig) -> jnp.ndarray:
    x32 = x
    if cfg.num_codebooks > 1:
        return jnp.stack(
            [dense(params["head"][c], x32) for c in range(cfg.num_codebooks)], axis=-2
        )  # [B, S, n_cb, V]
    if cfg.tie_embeddings:
        return x32 @ params["embed"]["embedding"].T.astype(x32.dtype)
    return dense(params["head"], x32)


def _inputs_to_h(params, batch, cfg: ModelConfig) -> jnp.ndarray:
    h = _embed_tokens(params, batch["tokens"], cfg)
    if cfg.frontend:
        pe = dense(params["frontend_proj"], batch["prefix_emb"].astype(cfg.dtype))
        h = jnp.concatenate([pe, h], axis=1)
    return h


def _final_hidden(params, batch, cfg: ModelConfig, shard: ShardHook,
                  use_window: bool = False):
    """The normed last hidden state ``[B, S, d]`` and the stack's aux (see
    ``stack_apply``)."""
    h = _inputs_to_h(params, batch, cfg)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = shard(h, "act_resid")
    h, _, aux = stack_apply(params["stack"], h, positions, cfg,
                            shard=shard, use_window=use_window)
    return rmsnorm(params["ln_f"], h, cfg.norm_eps), aux


def forward(
    params: Pytree,
    batch: dict,
    cfg: ModelConfig,
    *,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """Full-sequence forward.  Returns (logits_f32, aux_loss)."""
    h, aux = _final_hidden(params, batch, cfg, shard, use_window)
    logits = _head(params, h, cfg).astype(jnp.float32)
    return shard(logits, "logits"), _aux_terms(aux, cfg)[0]


def _aux_terms(aux, cfg: ModelConfig):
    """The stack's aux -> (aux loss, step counters).  MoE: the router's
    load-balancing loss times ``router_aux_loss_coef``, and the (token,
    slot) pairs routed to held experts and dropped; else the zero scalar and
    no counters."""
    if not isinstance(aux, dict):
        return aux, {}
    loss = cfg.router_aux_loss_coef * load_balancing_loss(aux, cfg.num_experts)
    return loss, {"moe_rows_held": aux["rows_held"],
                  "moe_rows_dropped": aux["rows_dropped"]}


def _masked_ce(logits: jnp.ndarray, labels: jnp.ndarray):
    """Returns (sum of -log p over unmasked labels, count)."""
    mask = (labels >= 0).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(lp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * mask), jnp.sum(mask)


def _sliced_ce(params, h: jnp.ndarray, labels: jnp.ndarray, nv: int,
               cfg: ModelConfig, shard: ShardHook):
    """Returns (sum of -log p over unmasked labels, count), with the head and
    its logits taken in ``nv`` vocabulary slices (see ``loss_fn``)."""
    # the head's rows [V, d]: the tied embedding, or the kernel's columns
    if cfg.tie_embeddings:
        w = params["embed"]["embedding"]
    else:
        w = params["head"]["kernel"].T
    V = w.shape[0]
    vc = -(-V // nv)
    pad = nv * vc - V
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    w = w.reshape(nv, vc, -1)
    offsets = jnp.arange(nv) * vc

    # closed over, not a carry (a carry is saved once per slice for the
    # backward); in f32, so the slices' shares of its cotangent add in f32
    h32 = h.astype(jnp.float32)

    def slice_stats(_, inp):
        ws, off = inp
        logits = h32.astype(cfg.dtype) @ ws.T.astype(cfg.dtype)
        logits = logits.astype(jnp.float32)
        logits = shard(logits, "logits")
        col = jnp.arange(vc)
        if pad:
            logits = jnp.where(off + col < V, logits, -jnp.inf)
        hit = col == (labels - off)[..., None]
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return None, (jax.nn.logsumexp(logits, axis=-1), picked)

    _, (lse, picked) = jax.lax.scan(
        jax.checkpoint(slice_stats), None, (w, offsets))
    mask = (labels >= 0).astype(jnp.float32)
    nll = jax.nn.logsumexp(lse, axis=0) - jnp.sum(picked, axis=0)
    return jnp.sum(nll * mask), jnp.sum(mask)


def loss_fn(
    params: Pytree,
    batch: dict,
    cfg: ModelConfig,
    *,
    shard: ShardHook = _id_hook,
) -> tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy with -1-masked labels (+ MoE aux loss, and
    the MoE step counters among the metrics).

    With ``cfg.ce_chunk > 0`` (and a single codebook) the LM head + CE run
    over ``nv = ceil(S / ce_chunk)`` vocabulary slices of ``ceil(V / nv)``
    rows inside a checkpointed scan: one slice's logits ``[B, S, V / nv]``
    hold as many elements as a ``[B, ce_chunk, V]`` chunk, and the [T, V]
    logits tensor is never materialized (fwd OR bwd).  Each slice's
    logsumexp and label logit come out of the scan, and the loss combines
    them.  The head's slices go in as the scan's ``xs``, not through its
    closure: the cotangent of ``xs`` is the stacked ``ys``, so each slice of
    the head's gradient is one matmul over all tokens, written once, where a
    closed-over head would be re-accumulated in a full ``[V, d]`` f32 carry
    once per iteration.  A V that the slices do not divide is padded, and
    its padding masked out of the logits; ``nv == 1`` is the unchunked loss.
    """
    labels = batch["labels"]
    nv = -(-labels.shape[1] // cfg.ce_chunk) if cfg.ce_chunk else 1
    h, aux = _final_hidden(params, batch, cfg, shard)
    if nv > 1 and cfg.num_codebooks == 1:
        s, c = _sliced_ce(params, h, labels, nv, cfg, shard)
    else:
        logits = shard(_head(params, h, cfg).astype(jnp.float32), "logits")
        s, c = _masked_ce(logits, labels)
    loss = s / jnp.maximum(c, 1.0)
    aux, counters = _aux_terms(aux, cfg)
    total = loss + aux
    return total, {"loss": loss, "aux": aux, **counters}


# --------------------------------------------------------------------- decode

def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16) -> Pytree:
    return stack_caches(cfg, batch, max_len, dtype)


def prefill(
    params: Pytree,
    batch: dict,
    caches: Pytree,
    cfg: ModelConfig,
    *,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """Process a prompt, filling caches.  Returns (last_logits, caches)."""
    h = _inputs_to_h(params, batch, cfg)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, caches, _ = stack_apply(
        params["stack"], h, positions, cfg,
        caches=caches, cache_index=0, shard=shard, use_window=use_window,
    )
    h = rmsnorm(params["ln_f"], h[:, -1:], cfg.norm_eps)
    logits = _head(params, h, cfg).astype(jnp.float32)
    return logits, caches


def decode_step(
    params: Pytree,
    tokens: jnp.ndarray,  # [B, 1] (musicgen: [B, 1, n_cb])
    caches: Pytree,
    index,                # scalar: position of this token
    cfg: ModelConfig,
    *,
    shard: ShardHook = _id_hook,
    use_window: bool = False,
):
    """One serving step: one new token against the cache.  Returns
    (logits [B,1,(n_cb,)V], new_caches)."""
    h = _embed_tokens(params, tokens, cfg)
    B = h.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(index)[None, None], (B, 1))
    h, caches, _ = stack_apply(
        params["stack"], h, positions, cfg,
        caches=caches, cache_index=index, shard=shard, use_window=use_window,
    )
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    logits = _head(params, h, cfg).astype(jnp.float32)
    return logits, caches


def param_count(params: Pytree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
