"""Round-mode server-algorithm registry on the flat slab layout.

The paper's point is that DuDe-ASGD is one *server update rule* among peers
(sync SGD, MIFA, FedBuff, the ASGD family).  This module is the single home
of those rules expressed on the engine's canonical flat layout — ``[P]``
vectors and ``[n, P]`` slabs in the segment-range split of a ``FlatSpec`` —
so the SAME math runs in both execution modes:

* the production train step (``launch/steps.py`` / ``api.Trainer``): one
  ``RoundAlgo`` per session, its server state living inside the single
  ``FlatTrainState`` and its round body running mesh-native (under the
  engine's P-axis ``shard_map`` when a mesh is given — every rule here is
  elementwise on P with worker-axis reductions local to each P-shard, so a
  sharded round moves zero bytes);
* the event-driven simulator (``core/simulator.py``): ``core/baselines.py``
  wraps the very same rule cores (``sync_direction`` / ``mifa_update`` /
  ``fedbuff_fold``) into per-arrival / per-round callbacks, making the
  simulator a thin scheduling shell over this registry.

A ``RoundAlgo`` consumes the per-round inputs of the semi-async SPMD driver
— the ``[n, P]`` fresh gradients plus the schedule's start/commit masks —
and produces the descent direction ``g`` and an ``applied`` gate:

  ``round(state, fresh, start_mask, commit_mask) -> (state, g, applied)``

``applied`` is a traced bool scalar gating the optimizer apply (FedBuff
holds the model until its buffer fills; everything else applies every
round).  The DuDe family does not go through ``round`` on the training hot
path: ``fused_apply=True`` tells the step builder to call
``DuDeEngine.round_apply`` instead, which fuses the round with the flat
optimizer apply in one shard_map (PR 3).  ``round`` is still provided for
every algo so equivalence tests and non-fused callers have one uniform
entry point.

Mask semantics per rule (all masks are ``[n]`` bool):

* ``dude`` / ``dude_accum`` — paper §3: ``start_mask`` latches the fresh
  gradient into ``inflight``, ``commit_mask`` folds ``inflight - g_workers``
  into ``g_bar`` (``DuDeEngine.round``).
* ``sync_sgd`` — ``commit_mask`` is the participation set; direction is the
  mean of participating workers' fresh gradients (Khaled & Richtarik 2023).
* ``mifa`` — participating workers (``commit_mask``) overwrite their row of
  the gradient memory; direction is the mean over ALL rows, stale entries
  included (Gu et al. 2021, no local updates).
* ``fedbuff`` — participating workers' fresh gradients fold into one ``[P]``
  accumulator; the model updates only when ``buffer_size`` gradients have
  arrived, with the buffered mean (Nguyen et al. 2022, K=1).

Alongside the round registry lives the ARRIVAL-granularity one:
``AsyncAlgo`` rules consume one worker's gradient per server iteration —
``arrival(state, worker, grad, tau) -> (state, g)`` — and carry the routing
discipline (greedy / uniform / shuffled) that the event loop
(``runtime/loop.py``) schedules.  ``dude`` maps to ``DuDeEngine.commit``;
the three ASGD disciplines are the identity rule under different routing;
the staleness-adaptive family (``dude_const`` / ``dude_hinge`` /
``dude_poly``) mixes the arriving gradient with the worker's stored slab row
by FedAsync's s(τ) weight before the DuDe commit — at s(τ)=1 it IS the dude
rule, bitwise.  These are what ``runtime.AsyncRunner`` and
``Trainer.run_async`` drive on the flat train state, and what
``core/baselines.py`` wraps for the simulator.  Covered by docs/engine.md
("The server-rule registry and the session API") and docs/async.md
("Arrival-granularity algorithms" / "Staleness-adaptive rules").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from .engine import DuDeEngine, EngineState

Pytree = Any

__all__ = [
    "ROUND_ALGOS", "RoundAlgo", "make_round_algo",
    "ASYNC_ALGOS", "STALENESS_RULES", "STALENESS_ASYNC",
    "AsyncAlgo", "make_async_algo", "staleness_weight",
    "sync_direction", "mifa_update", "fedbuff_fold",
]

# every name the production driver / Trainer accepts for --algo (round mode)
ROUND_ALGOS = ("dude", "dude_accum", "sync_sgd", "mifa", "fedbuff")

# arrival-granularity rules (--async mode); dude appears in both registries
ASYNC_ALGOS = ("dude", "dude_const", "dude_hinge", "dude_poly",
               "vanilla_asgd", "uniform_asgd", "shuffled_asgd")

# FedAsync staleness weight vocabulary and the async algo names that use it
STALENESS_RULES = ("const", "hinge", "poly")
STALENESS_ASYNC = {"dude_const": "const", "dude_hinge": "hinge",
                   "dude_poly": "poly"}

# FedAsync / FLGo defaults for the s(tau) shapes
HINGE_A = 10.0
HINGE_B = 4.0
POLY_A = 0.5


def staleness_weight(rule: str, tau, *, hinge_a: float = HINGE_A,
                     hinge_b: float = HINGE_B, poly_a: float = POLY_A):
    """FedAsync's staleness weight s(τ) ∈ (0, 1] (Xie et al. 2019).

    ``const``: s(τ) = 1 (plain DuDe).  ``hinge``: s(τ) = 1 for τ <= b, else
    ``min(1, 1 / (a(τ - b)))`` — the min also closes the 1/0 hole just past
    the knee, so the weight is finite, in (0, 1], and monotone
    non-increasing for every τ >= 0.  ``poly``: s(τ) = (1 + τ)^(-a).
    Elementwise jnp on float32, so the rule runs inside the mesh-native
    arrival step; accepts scalars or arrays (the property tests sweep
    arrays).
    """
    tau = jnp.asarray(tau, jnp.float32)
    if rule == "const":
        return jnp.ones_like(tau)
    if rule == "hinge":
        a, b = jnp.float32(hinge_a), jnp.float32(hinge_b)
        return jnp.where(tau <= b, jnp.float32(1.0),
                         jnp.minimum(jnp.float32(1.0),
                                     jnp.float32(1.0) / (a * (tau - b))))
    if rule == "poly":
        return jnp.power(jnp.float32(1.0) + tau, -jnp.float32(poly_a))
    raise ValueError(
        f"unknown staleness rule {rule!r}; options: {STALENESS_RULES}")


# ------------------------------------------------------------- rule cores
#
# The pure math, shared verbatim with core/baselines.py (the simulator's
# per-arrival wrappers).  All operate on flat f32 slabs and are elementwise
# on P; worker-axis reductions are local to any contiguous P-shard.

def sync_direction(fresh: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean of the participating rows of ``fresh`` ``[n, P]`` -> ``[P]``."""
    m = mask.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    return jnp.sum(fresh.astype(jnp.float32) * m[:, None], axis=0) / cnt


def mifa_update(memory: jnp.ndarray, fresh: jnp.ndarray, mask: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MIFA gradient memory update: participating rows refresh, direction is
    the mean over all n rows (stale entries included)."""
    memory = jnp.where(mask[:, None], fresh.astype(jnp.float32), memory)
    return memory, jnp.mean(memory, axis=0)


def fedbuff_fold(acc: jnp.ndarray, count: jnp.ndarray, grad_sum: jnp.ndarray,
                 k: jnp.ndarray, buffer_size: int):
    """Fold ``k`` arrived gradients (summed into ``grad_sum``) into the
    FedBuff accumulator; flush when the buffer holds >= ``buffer_size``.

    Returns ``(acc', count', g, applied)`` — ``g`` is the buffered mean
    (meaningful only when ``applied``), and the accumulator resets on flush.
    Used per-arrival by the simulator (k=1, flush exactly at buffer_size, so
    the mean divides by buffer_size as in the paper) and per-round by the
    production step (k = |commit set|, which may overshoot the buffer within
    one round — the mean then divides by the actual count).
    """
    acc = acc + grad_sum.astype(jnp.float32)
    count = count + k.astype(jnp.int32)
    applied = count >= buffer_size
    g = acc / jnp.maximum(count, 1).astype(jnp.float32)
    zero = jnp.zeros((), jnp.int32)
    return (jnp.where(applied, jnp.zeros_like(acc), acc),
            jnp.where(applied, zero, count), g, applied)


# --------------------------------------------------------------- registry


@dataclasses.dataclass(frozen=True)
class RoundAlgo:
    """One server update rule bound to an engine, for the round-based
    production path.

    ``init()`` builds the rule's server state as flat slabs (an
    ``EngineState`` for the DuDe family; smaller slab tuples for the
    baselines) — it is the ``server`` field of the session's single
    ``FlatTrainState``.  ``round(state, fresh, sm, cm)`` advances it one
    semi-async round.  When ``fused_apply`` is set the step builder skips
    ``round`` and calls ``engine.round_apply`` (round + flat optimizer apply
    in one shard_map / Pallas pass) — the gate is then always-applied.
    """

    name: str
    engine: DuDeEngine
    fused_apply: bool
    init_fn: Callable[[], Pytree]
    # (state, fresh [n, P], start_mask, commit_mask)
    #   -> (state, g [P] f32, applied scalar bool)
    round_fn: Callable[..., tuple]
    # abstract server state for lowering; None = eval_shape(init_fn)
    state_shapes_fn: Callable[[], Pytree] = None

    def init(self) -> Pytree:
        return self.init_fn()

    def state_shapes(self) -> Pytree:
        """Abstract (ShapeDtypeStruct) server state, for lowering."""
        if self.state_shapes_fn is not None:
            return self.state_shapes_fn()
        return jax.eval_shape(self.init_fn)

    def round(self, state, fresh, start_mask, commit_mask):
        return self.round_fn(state, fresh,
                             start_mask.astype(bool), commit_mask.astype(bool))

    # -------------------------------------------------- shard_map plumbing

    def _shard(self, body, in_kinds: tuple, out_kinds: tuple):
        """Run ``body`` under the engine's P-axis shard_map when meshed.

        Kinds: ``"vec"`` = ``[.., P]`` sharded on the last axis, ``"row"`` =
        ``[n, P]`` sharded on P, ``"repl"`` = replicated.  Every rule body is
        elementwise on P (worker reductions stay inside the shard), so the
        sharded round is collective-free, exactly like the DuDe engine's.
        """
        eng = self.engine
        if eng.mesh is None:
            return body
        kind = {"vec": PartitionSpec(eng.paxes),
                "row": PartitionSpec(None, eng.paxes),
                "repl": PartitionSpec()}
        out = tuple(kind[k] for k in out_kinds)
        return shard_map(body, mesh=eng.mesh,
                         in_specs=tuple(kind[k] for k in in_kinds),
                         out_specs=out if len(out) > 1 else out[0],
                         check_vma=False)


def _make_dude(engine: DuDeEngine, name: str) -> RoundAlgo:
    def round_fn(state: EngineState, fresh, sm, cm):
        state, g_bar = engine.round(state, fresh, sm, cm)
        return state, g_bar, jnp.array(True)

    return RoundAlgo(name, engine, fused_apply=True,
                     init_fn=engine.init, round_fn=round_fn,
                     state_shapes_fn=engine.state_shapes)


def _make_sync(engine: DuDeEngine) -> RoundAlgo:
    def round_fn(state, fresh, sm, cm):
        body = algo._shard(sync_direction, ("row", "repl"), ("vec",))
        return state, body(fresh, cm), jnp.array(True)

    algo = RoundAlgo("sync_sgd", engine, fused_apply=False,
                     init_fn=lambda: (), round_fn=round_fn)
    return algo


def _make_mifa(engine: DuDeEngine) -> RoundAlgo:
    n, P = engine.n_workers, engine.P

    def init_fn():
        return jnp.zeros((n, P), jnp.float32)

    def round_fn(memory, fresh, sm, cm):
        body = algo._shard(mifa_update, ("row", "row", "repl"), ("row", "vec"))
        memory, g = body(memory, fresh, cm)
        return memory, g, jnp.array(True)

    algo = RoundAlgo("mifa", engine, fused_apply=False,
                     init_fn=init_fn, round_fn=round_fn)
    return algo


def _make_fedbuff(engine: DuDeEngine, buffer_size: int = 4) -> RoundAlgo:
    P = engine.P

    def init_fn():
        return (jnp.zeros((P,), jnp.float32), jnp.zeros((), jnp.int32))

    def masked_sum(fresh, cm):
        return jnp.sum(fresh.astype(jnp.float32)
                       * cm.astype(jnp.float32)[:, None], axis=0)

    def round_fn(state, fresh, sm, cm):
        acc, count = state
        body = algo._shard(masked_sum, ("row", "repl"), ("vec",))
        # scalar bookkeeping stays outside the shard_map (replicated); the
        # accumulator fold/reset is elementwise on the sharded [P] slab.
        acc, count, g, applied = fedbuff_fold(
            acc, count, body(fresh, cm), jnp.sum(cm.astype(jnp.int32)),
            buffer_size)
        return (acc, count), g, applied

    algo = RoundAlgo("fedbuff", engine, fused_apply=False,
                     init_fn=init_fn, round_fn=round_fn)
    return algo


def make_round_algo(name: str, engine: DuDeEngine,
                    buffer_size: int = 4) -> RoundAlgo:
    """Build the named server rule bound to ``engine``.

    The DuDe family requires the engine's ``accumulate`` flag to match the
    name (``dude_accum`` = the beyond-paper running-mean latch, reference
    backend only — enforced by ``DuDeEngine`` itself and, earlier, by
    ``api.TrainerConfig``).
    """
    if name in ("dude", "dude_accum"):
        want = name == "dude_accum"
        if engine.accumulate != want:
            raise ValueError(
                f"algo {name!r} needs an engine with accumulate={want}, "
                f"got accumulate={engine.accumulate}")
        return _make_dude(engine, name)
    if name == "sync_sgd":
        return _make_sync(engine)
    if name == "mifa":
        return _make_mifa(engine)
    if name == "fedbuff":
        return _make_fedbuff(engine, buffer_size=buffer_size)
    raise ValueError(f"unknown round algo {name!r}; options: {ROUND_ALGOS}")


# -------------------------------------------- arrival-granularity registry


@dataclasses.dataclass(frozen=True)
class AsyncAlgo:
    """One per-arrival server rule bound to an engine, for the fully-async
    path (``runtime.AsyncRunner`` / ``Trainer.run_async``).

    ``arrival(state, worker, grad, tau)`` consumes ONE worker's flat ``[P]``
    gradient (with its model staleness ``tau``, which only the
    staleness-adaptive rules read — it defaults to 0 for callers that
    predate it) and returns ``(state, g)`` — the descent direction the flat
    optimizer applies that same iteration.  The rule body is elementwise on
    P (``DuDeEngine.commit`` runs under the engine's P-axis ``shard_map``
    when meshed; the ASGD identity needs no collective at all; the
    staleness mix reads the worker's ``[n, P]`` row along the REPLICATED
    worker axis), so a sharded arrival step moves zero bytes, exactly like
    the round rules.

    ``route`` is the SCHEDULING half of the algorithm — who receives the
    post-update model — consumed by ``runtime.loop.drive_arrivals``:
    ``None`` (greedy: the arriving worker restarts on the freshest model,
    vanilla ASGD / DuDe), ``"uniform"`` (Koloskova et al. 2022) or
    ``"shuffled"`` (Islamov et al. 2024) routing.
    """

    name: str
    engine: DuDeEngine
    route: Any                        # None | "uniform" | "shuffled"
    init_fn: Callable[[], Pytree]
    # (state, worker i32 scalar, grad [P] f32, tau i32 scalar)
    #   -> (state, g [P] f32)
    arrival_fn: Callable[..., tuple]
    state_shapes_fn: Callable[[], Pytree] = None

    def init(self) -> Pytree:
        return self.init_fn()

    def state_shapes(self) -> Pytree:
        """Abstract (ShapeDtypeStruct) server state, for lowering."""
        if self.state_shapes_fn is not None:
            return self.state_shapes_fn()
        return jax.eval_shape(self.init_fn)

    def arrival(self, state, worker, grad, tau=0):
        return self.arrival_fn(state, jnp.asarray(worker, jnp.int32),
                               grad.astype(jnp.float32),
                               jnp.asarray(tau, jnp.int32))


def make_async_algo(name: str, engine: DuDeEngine) -> AsyncAlgo:
    """Build the named arrival-granularity rule bound to ``engine``.

    ``dude`` is the paper's Algorithm 1 server iteration
    (``DuDeEngine.commit``: fold ``(g - g_workers[w]) / n`` into ``g_bar``,
    remember ``g`` as worker ``w``'s latest) — greedy scheduling, full
    aggregation.  The three ASGD disciplines all descend along the raw
    arriving gradient and differ only in routing.  The staleness-adaptive
    family damps a stale arrival toward the worker's stored row before the
    commit:

        g_eff = s(τ)·g + (1 − s(τ))·g_workers[w]        (FedAsync mixing)

    so the fold becomes ``s(τ)·(g − g_workers[w]) / n`` — at s=1 the rule
    IS ``dude`` bitwise, and a maximally stale gradient barely perturbs the
    dual-delayed average.  The mix reads the worker's row in f32, so these
    rules require the uncompressed slab (``commit_format="f32"``, enforced
    here and at ``TrainerConfig`` build time).
    """
    if name == "dude" or name in STALENESS_ASYNC:
        if engine.accumulate:
            raise ValueError(
                f"async {name} runs per-arrival commits; the accumulate "
                "running-mean latch is a round-mode (dude_accum) feature")
        if name == "dude":
            def dude_arrival(state: EngineState, worker, grad, tau):
                return engine.commit(state, worker, grad)

            return AsyncAlgo("dude", engine, route=None,
                             init_fn=engine.init, arrival_fn=dude_arrival,
                             state_shapes_fn=engine.state_shapes)

        rule = STALENESS_ASYNC[name]
        if engine.codec.compressed:
            raise ValueError(
                f"async {name} mixes the arriving gradient with the stored "
                f"f32 slab row; it requires commit_format='f32', not "
                f"{engine.codec.format!r}")

        def staleness_arrival(state: EngineState, worker, grad, tau):
            s = staleness_weight(rule, tau)
            # row gather along the REPLICATED worker axis of the [n, P]
            # slab: with P-axis sharding this slices shard-locally, keeping
            # the arrival step collective-free (asserted by
            # tests/test_scenarios.py on the 8-device mesh)
            old = jax.lax.dynamic_index_in_dim(
                state.g_workers, worker, axis=0, keepdims=False
            ).astype(jnp.float32)
            g_eff = s * grad + (jnp.float32(1.0) - s) * old
            return engine.commit(state, worker, g_eff)

        return AsyncAlgo(name, engine, route=None,
                         init_fn=engine.init, arrival_fn=staleness_arrival,
                         state_shapes_fn=engine.state_shapes)
    if name in ("vanilla_asgd", "uniform_asgd", "shuffled_asgd"):
        route = {"vanilla_asgd": None, "uniform_asgd": "uniform",
                 "shuffled_asgd": "shuffled"}[name]

        def asgd_arrival(state, worker, grad, tau):
            return state, grad

        return AsyncAlgo(name, engine, route=route,
                         init_fn=lambda: (), arrival_fn=asgd_arrival)
    raise ValueError(f"unknown async algo {name!r}; options: {ASYNC_ALGOS}")
