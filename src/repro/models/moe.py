"""Mixture-of-Experts FFN: a softmax router over every expert of the layer,
top-k, and drop-free sort-based dispatch to the experts the layer holds.

The expert share.  A layer may hold only experts ``[expert_offset,
expert_offset + experts_held)`` of its ``num_experts`` (one chip's share
under expert parallelism), stacked as ``[held, d, f]``.  The router keeps
its full width, every token picks its top-k over all experts, and the layer
computes only its own experts' part of the output: a token whose top-k
picks no held expert gets nothing from it.  The parts of all shares add up
to the whole layer (a shared expert is every share's own).

Dispatch.  The (token, slot) pairs are sorted by held expert, the token row
of each pair is gathered in that order, the experts run as grouped matmuls
(``jax.lax.ragged_dot``) over the sorted rows, and each token gathers its
slots' outputs back through the inverse order, weighted by its gates.  The
sorted buffer has a row for every pair that can reach a held expert
(``T * min(k, held)``), so no pair is ever dropped; its rows past the held
pairs compute to zero.  Nothing is scattered.

Under ``vmap`` with the weights shared (the round step's per-worker
backward) the workers' pairs are dispatched as one set: the dispatch folds
the mapped axis into its worker axis and sorts by (expert, worker), so each
expert's matmul takes every worker's rows at once while each worker's
weight gradient is a group of its own.  Each worker's loss and router
statistics stay its own.  Plain autodiff cannot run there: ``ragged_dot``
has no batching rule for mapped group sizes (``NotImplementedError``), and
a ``custom_vmap`` function cannot be transposed; so the backward is written
out (``custom_vjp``) and both halves carry their own ``custom_vmap`` rule.

The router statistics of every layer are summed over the stack, and
``load_balancing_loss`` takes them as Hugging Face's
``load_balancing_loss_func`` takes the concatenation of every layer's router
logits: ``E * sum_e f_e * P_e``, with ``f_e`` the share of (token, slot)
assignments to expert ``e`` and ``P_e`` its mean router probability.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from ..runtime import spans
from .layers import dense, dense_init

Pytree = Any
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    num_experts: int       # the router's width: every expert of the layer
    experts_per_tok: int
    d_ff: int              # per-expert hidden dim
    experts_held: int = 0  # experts this layer holds (0: all of them) ...
    expert_offset: int = 0  # ... starting at this one
    norm_topk_prob: bool = True
    num_shared_experts: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


def moe_init(key, cfg: MoEConfig) -> Pytree:
    kr, ku, kg, kd, ks = jax.random.split(key, 5)
    E, H, d, f = cfg.num_experts, cfg.held, cfg.d_model, cfg.d_ff
    scale = 1.0 / jnp.sqrt(d)

    def stack(k, shape, sc):
        return jax.random.normal(k, shape, jnp.float32) * sc

    p = {
        "router": dense_init(kr, d, E, scale=0.02),
        "wup": stack(ku, (H, d, f), scale),
        "wgate": stack(kg, (H, d, f), scale),
        "wdown": stack(kd, (H, f, d), 1.0 / jnp.sqrt(f)),
    }
    if cfg.num_shared_experts:
        p["shared"] = {
            "up": dense_init(jax.random.fold_in(ks, 0), d, f * cfg.num_shared_experts),
            "gate": dense_init(jax.random.fold_in(ks, 1), d, f * cfg.num_shared_experts),
            "down": dense_init(jax.random.fold_in(ks, 2), f * cfg.num_shared_experts, d),
        }
    return p


def moe_apply(p: Pytree, x: jnp.ndarray, cfg: MoEConfig):
    """x: [B, S, d].  Returns ``(y, stats)``: the held experts' (and the
    shared experts') output, and the router statistics ``moe_stats_zero``
    describes."""
    B, S, d = x.shape
    T = B * S
    E, k, H = cfg.num_experts, cfg.experts_per_tok, cfg.held
    xf = x.reshape(T, d)
    with jax.named_scope(spans.MOE_ROUTE):
        logits = jnp.dot(xf.astype(jnp.float32), p["router"]["kernel"],
                         precision=HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)             # [T, E]
        gates, topi = jax.lax.top_k(probs, k)               # [T, k]
        if cfg.norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        slot = topi - cfg.expert_offset
        held = (slot >= 0) & (slot < H)
        rows = jnp.sum(held).astype(jnp.float32)
        stats = {
            "count": jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                             axis=(0, 1)),
            "prob": jnp.sum(probs, axis=0),
            "tokens": jnp.float32(T),
            "rows_held": rows,
            # pairs routed here that the buffer has no row for: 0, as top-k
            # picks distinct experts; kept as the step's on-device witness
            # of drop-free dispatch
            "rows_dropped": jnp.maximum(rows - T * min(k, H), 0.0),
        }
    w = [p[n].astype(x.dtype) for n in ("wgate", "wup", "wdown")]
    y = _experts(xf, jnp.where(held, gates, 0.0),
                 jnp.where(held, slot, H).astype(jnp.int32), *w)
    if "shared" in p:
        sh = p["shared"]
        hs = jax.nn.silu(dense(sh["gate"], xf)) * dense(sh["up"], xf)
        y = y + dense(sh["down"], hs)
    return y.reshape(B, S, d), stats


def moe_stats_zero(num_experts: int) -> dict:
    """Router statistics of no layer: per expert, the (token, slot)
    assignments (``count``) and the summed router probability (``prob``);
    the tokens routed; the pairs routed to a held expert, and of those the
    ones dropped."""
    z = jnp.zeros((), jnp.float32)
    return {"count": jnp.zeros((num_experts,), jnp.float32),
            "prob": jnp.zeros((num_experts,), jnp.float32), "tokens": z,
            "rows_held": z, "rows_dropped": z}


def load_balancing_loss(stats: dict, num_experts: int) -> jnp.ndarray:
    """``E * sum_e f_e * P_e`` over the layers' summed statistics."""
    n = jnp.maximum(stats["tokens"], 1.0)
    f = jax.lax.stop_gradient(stats["count"]) / n
    return num_experts * jnp.sum(f * stats["prob"] / n)


# ----------------------------------------------------- the held experts

@jax.custom_vjp
def _experts(x, gates, slot, wg, wu, wd):
    """The held experts' SwiGLU, gate-weighted, per token: ``x [T, d]``,
    ``gates [T, k]`` (0 where the pair is not held), ``slot [T, k]`` (the
    held expert of each pair, ``held`` where none)."""
    return _fwd_op(x[None], gates[None], slot[None], wg, wu, wd)[0][0]


def _experts_fwd(x, gates, slot, wg, wu, wd):
    y, xs, a, b = _fwd_op(x[None], gates[None], slot[None], wg, wu, wd)
    return y[0], (x, gates, slot, xs, a, b, wg, wu, wd)


def _experts_bwd(res, dy):
    x, gates, slot, xs, a, b, wg, wu, wd = res
    dx, dgates, dwg, dwu, dwd = _bwd_op(dy[None], x[None], gates[None],
                                        slot[None], xs, a, b, wg, wu, wd)
    return dx[0], dgates[0], None, dwg[0], dwu[0], dwd[0]


_experts.defvjp(_experts_fwd, _experts_bwd)


def _sort(slot, held: int):
    """Pairs of ``slot [n, T, k]`` sorted by (held expert, worker), the pairs
    of no held expert last.  Returns ``(order, inverse, token, sizes)``:
    the sorted pairs' flat indices and each pair's place in that order, the
    flat token (``[n*T]``) of each of the first ``n*T*min(k, held)`` sorted
    pairs, and the group sizes per (expert, worker), ``[held * n]``."""
    n, T, k = slot.shape
    worker = jnp.arange(n, dtype=jnp.int32)[:, None, None]
    order = jnp.argsort((slot * n + worker).reshape(-1), stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(jax.nn.one_hot(slot, held, dtype=jnp.int32), axis=(1, 2))
    rows = n * T * min(k, held)
    return order, inverse, order[:rows] // k, sizes.T.reshape(-1)


def _gmm(lhs, w, sizes):
    """Grouped matmul: row block ``g`` of ``lhs`` times ``w[g]``."""
    with jax.named_scope(spans.MOE_EXPERTS):
        return jax.lax.ragged_dot(lhs, w, sizes)


def _tgmm(lhs, rhs, sizes, dtype):
    """Per group ``g``: ``lhs[rows of g]^T @ rhs[rows of g]`` -> ``[G, ., .]``
    (the weight gradient of ``_gmm``), accumulated in f32."""
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    with jax.named_scope(spans.MOE_EXPERTS):
        return jax.lax.ragged_dot_general(
            lhs, rhs, sizes, dn,
            preferred_element_type=jnp.float32).astype(dtype)


def _per_worker(dw, held: int, n: int):
    """``[held * n, ...]`` (expert-major groups) -> ``[n, held, ...]``."""
    return jnp.swapaxes(dw.reshape((held, n) + dw.shape[1:]), 0, 1)


def _fold_rule(op, n_mapped: int):
    """The ``custom_vmap`` rule of ``op``: its first ``n_mapped`` arguments
    carry a leading worker axis, into which the mapped axis folds; the
    weights (the other arguments) must be shared."""
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_mapped:]):
            raise NotImplementedError(
                "MoE dispatch under vmap needs the expert weights shared "
                "(in_axes=None)")
        args = [v if b or i >= n_mapped
                else jnp.broadcast_to(v, (axis_size,) + v.shape)
                for i, (v, b) in enumerate(zip(args, in_batched))]
        outs = op(*[v.reshape((-1,) + v.shape[2:]) if i < n_mapped else v
                    for i, v in enumerate(args)])
        return (tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs),
                (True,) * len(outs))
    return rule


@custom_vmap
def _fwd_op(x, gates, slot, wg, wu, wd):
    """``x [n, T, d]``, ``gates`` and ``slot [n, T, k]`` -> ``y [n, T, d]``
    and the sorted rows ``xs [n*R, d]``, ``a``, ``b [n*R, f]`` (the gate
    and up projections) for the backward, ``R = T * min(k, held)``."""
    n, T, d = x.shape
    k, held = slot.shape[-1], wg.shape[0]
    with jax.named_scope(spans.MOE_ROUTE):
        order, inverse, token, fine = _sort(slot, held)
        sizes = fine.reshape(held, n).sum(axis=1)
        xs = x.reshape(n * T, d)[token]
    a, b = _gmm(xs, wg, sizes), _gmm(xs, wu, sizes)
    with jax.named_scope(spans.MOE_EXPERTS):
        h = jax.nn.silu(a) * b
    o = _gmm(h, wd, sizes)
    with jax.named_scope(spans.MOE_ROUTE):
        g = gates.reshape(-1)[order[:o.shape[0]]][:, None]
        o = (g * o).astype(x.dtype)
        # each pair's weighted row; a pair of no held expert may sort past
        # the buffer, so it takes a zero row, not the clamped last one
        at = jnp.minimum(inverse, o.shape[0] - 1)
        hit = (slot < held).reshape(-1)
        o = jnp.where(hit[:, None], o[at], 0).reshape(n * T, k, d)
        y = jnp.sum(o, axis=1, dtype=jnp.float32)
    return y.reshape(n, T, d).astype(x.dtype), xs, a, b


@custom_vmap
def _bwd_op(dy, x, gates, slot, xs, a, b, wg, wu, wd):
    """Cotangents of ``_fwd_op``'s ``y`` -> of ``x``, ``gates`` (per
    worker) and of the weights (``[n, held, ...]``, one per worker)."""
    n, T, d = x.shape
    k, held = slot.shape[-1], wg.shape[0]
    dtype = x.dtype
    with jax.named_scope(spans.MOE_ROUTE):
        order, inverse, token, fine = _sort(slot, held)
        sizes = fine.reshape(held, n).sum(axis=1)
        rows = token.shape[0]
        dys = dy.reshape(n * T, d)[token]
        g = gates.reshape(-1)[order[:rows]][:, None]        # [R, 1] f32
    dyh = _gmm(dys, jnp.swapaxes(wd, 1, 2), sizes)          # dy_rows @ wd^T
    with jax.named_scope(spans.MOE_EXPERTS):
        a32, b32, dyh32 = (v.astype(jnp.float32) for v in (a, b, dyh))
        sig = jax.nn.sigmoid(a32)
        h = (a32 * sig * b32).astype(dtype)
        dg = jnp.sum(a32 * sig * b32 * dyh32, axis=-1)
        dh = g * dyh32
        da = (dh * b32 * sig * (1.0 + a32 * (1.0 - sig))).astype(dtype)
        db = (dh * a32 * sig).astype(dtype)
    dwd = _tgmm(h, (g * dys).astype(dtype), fine, wd.dtype)
    dwg = _tgmm(xs, da, fine, wg.dtype)
    dwu = _tgmm(xs, db, fine, wu.dtype)
    dxs = (_gmm(da, jnp.swapaxes(wg, 1, 2), sizes)
           + _gmm(db, jnp.swapaxes(wu, 1, 2), sizes))
    with jax.named_scope(spans.MOE_ROUTE):
        at = jnp.minimum(inverse, rows - 1)
        hit = (slot < held).reshape(-1)
        dx = jnp.where(hit[:, None], dxs[at], 0).reshape(n * T, k, d)
        dx = jnp.sum(dx, axis=1, dtype=jnp.float32).astype(dtype)
        dgates = jnp.where(hit, dg[at], 0.0).reshape(n, T, k)
    return (dx.reshape(n, T, d), dgates.astype(gates.dtype),
            _per_worker(dwg, held, n), _per_worker(dwu, held, n),
            _per_worker(dwd, held, n))


_fwd_op.def_vmap(_fold_rule(_fwd_op, 3))
_bwd_op.def_vmap(_fold_rule(_bwd_op, 7))
