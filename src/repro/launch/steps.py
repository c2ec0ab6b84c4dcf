"""Jitted step builders for the production path (DESIGN.md mode B) and the
serving path, plus ShapeDtypeStruct ``input_specs`` for the dry-run.
(The session layer over these builders — one object, one state, one step
signature — is ``repro.api``; new callers should start there.)

train_step semantics (semi-async round):
  1. every worker group computes the gradient of the live model on its own
     heterogeneous shard — one vmapped backward, worker axis leading;
  2. the server rule (a ``core.algos.RoundAlgo``: the DuDe engine round, or
     a round-based Table-1 baseline on the same slabs) consumes the fresh
     ``[n, P]`` gradients and the host-precomputed start/commit masks;
  3. the flat optimizer applies the rule's direction g^t on the ``[P]``
     master params — fused into the round for the DuDe family
     (``engine.round_apply``), gated by the rule's ``applied`` flag
     otherwise (FedBuff holds the model while its buffer fills).

The canonical train state is the flat ``FlatTrainState`` (master params +
optimizer slots + server slabs, all padded ``[P]``/``[n, P]`` vectors),
sharded on the P axis by the segment ranges of the ``FlatSpec`` shard
table.  The stacked gradients are raveled to the same ``[n, P]`` layout
right after the vmapped backward; with ``constrain_grads`` the ravel
happens INSIDE a ``with_sharding_constraint`` pinned to the slab sharding,
so GSPMD emits a reduce-scatter straight into the shard each device owns
instead of all-reduce + local slice.  With ``params_layout="tp"`` the
params never leave their P-shards at all: the forward is fed through the
TP-native exchange (``FlatSpec.unravel_sharded``) and the gradients come
back through its reverse (``ravel_stacked_sharded``) — no device ever
holds the full ``[P]`` vector or a replicated ``[n, P]`` slab (docs/
engine.md, "TP-native unravel").  The legacy pytree-tuple signature and
the ``flat_optimizer=`` keyword shim are RETIRED: the flat step is the only
step (held tuple states convert once via ``flat_state_from_legacy``; see
the migration table in docs/api.md).  The per-arrival async path lives in
``runtime/runner.py`` over the same state.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.algos import RoundAlgo, make_round_algo
from ..core.dude import DuDeConfig
from ..core.engine import DuDeEngine, EngineState
from ..core.flatten import make_flat_spec
from ..models import decode_step as model_decode_step
from ..models import forward, init_decode_caches, lm_init, loss_fn, prefill
from ..models.config import ModelConfig
from ..models.stubs import token_shape
from ..optim import FlatOptState, FlatTrainState, OptState, flat_twin, sgd
from ..runtime import spans
from ..sharding import (
    batch_sharding,
    cache_shardings,
    dude_state_shardings,
    flat_train_state_shardings,
    make_shard_hook,
    param_shardings,
)

Pytree = Any

INPUT_SHAPES = {
    "train_4k": {"seq_len": 4096, "global_batch": 256, "kind": "train"},
    "prefill_32k": {"seq_len": 32768, "global_batch": 32, "kind": "prefill"},
    "decode_32k": {"seq_len": 32768, "global_batch": 128, "kind": "decode"},
    "long_500k": {"seq_len": 524288, "global_batch": 1, "kind": "decode"},
}


def shape_supported(cfg: ModelConfig, shape_name: str) -> tuple[bool, str]:
    """long_500k only for sub-quadratic decode archs (DESIGN.md §4)."""
    if shape_name == "long_500k" and not cfg.supports_long_decode():
        return False, (
            f"{cfg.name}: full attention without sliding window — long_500k "
            "skipped (DESIGN.md §4)"
        )
    return True, ""


# ------------------------------------------------------------- step builders

PARAMS_LAYOUTS = ("replicated", "tp")
# what each worker's loss_fn metrics give the round step's metrics
STEP_METRICS = ("loss", "moe_rows_held", "moe_rows_dropped")


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    """Beyond-paper §Perf knobs (defaults == paper-faithful baseline)."""
    grad_dtype: Any = None        # ravel the stacked grads in this dtype
                                  # (bf16 halves the gradient-reduction
                                  # payload feeding the DuDe buffers)
    constrain_grads: bool = False  # wrap the grad ravel in a
                                   # with_sharding_constraint pinned to the
                                   # engine's [n, P] slab sharding so GSPMD
                                   # emits reduce-scatter into the owned
                                   # shard instead of all-reduce + slice
    backend: str = "reference"     # ServerEngine update path for the DuDe
                                   # round: reference | indexed | pallas
    shard_engine: bool = True      # P-axis shard the EngineState over the
                                   # mesh and run the round under shard_map
                                   # (mesh-native engine); False keeps the
                                   # engine layout up to GSPMD
    params_layout: str = "replicated"  # how the forward gets its params:
                                   # "replicated" — one [P] all-gather per
                                   # step, then local slices (correctness
                                   # oracle; O(P) HBM per device);
                                   # "tp" — TP-native exchange straight
                                   # from the P-shards into the Megatron-TP
                                   # leaf layout, no full [P] anywhere
                                   # (needs a mesh-native engine)
    commit_format: str = "f32"     # slab storage / commit wire format:
                                   # "f32" | "int8_ef" | "topk_ef"
                                   # (core/compression.py; docs/engine.md
                                   # "Compressed slabs")
    sparse_transport: bool = False  # topk_ef only: carry commits as
                                   # index-carrying SparseRows and keep
                                   # touched-tile bitmaps on the engine
                                   # state, so commit ingress and the round
                                   # fold scale O(k * tiles_touched) instead
                                   # of O(P) (docs/engine.md "Sparse commit
                                   # transport")
    sparse_cap: Optional[int] = None  # static touched-tile slots per
                                   # SparseRow (None = all tiles; smaller
                                   # caps bound wire bytes, overflow
                                   # re-enters through error feedback)

    def __post_init__(self):
        if self.params_layout not in PARAMS_LAYOUTS:
            raise ValueError(
                f"unknown params_layout {self.params_layout!r}; "
                f"options: {PARAMS_LAYOUTS}")
        if self.sparse_transport and self.commit_format != "topk_ef":
            raise ValueError(
                "sparse_transport requires commit_format='topk_ef' (the "
                f"other formats have dense payloads), got "
                f"{self.commit_format!r}")
        if self.sparse_cap is not None and not self.sparse_transport:
            raise ValueError("sparse_cap requires sparse_transport=True")


def make_engine(cfg: ModelConfig, mesh=None,
                dude_cfg: Optional[DuDeConfig] = None,
                options: TrainOptions = TrainOptions()) -> DuDeEngine:
    """The ServerEngine the train step runs — mesh-native when a mesh is
    given and ``options.shard_engine``: the flat spec is built shard-aligned
    (``mesh_axis_size`` = total device count) and every round runs under
    shard_map with the P axis split by segment ranges across ALL mesh axes
    (the DuDe slabs are pure elementwise state, so the full mesh shards
    them regardless of the params' TP/FSDP layout)."""
    dude_cfg = dude_cfg or DuDeConfig(cfg.n_workers, cfg.dude_buffer_dtype)
    engine_mesh = mesh if (mesh is not None and options.shard_engine) else None
    paxes = None
    if engine_mesh is not None:
        # 'data' leads the P-axis hierarchy so the explicit gradient
        # reduce-scatter (constrain_grads) lands chunks in engine order
        paxes = tuple(sorted(engine_mesh.axis_names,
                             key=lambda a: (a != "data",)))
    return DuDeEngine.for_tree(
        abstract_params(cfg), dude_cfg.n_workers,
        buffer_dtype=dude_cfg.buffer_dtype or jnp.float32,
        accumulate=dude_cfg.accumulate, backend=options.backend,
        mesh=engine_mesh, axis_name=paxes,
        commit_format=options.commit_format,
        sparse_meta=options.sparse_transport,
        sparse_cap=options.sparse_cap,
    )


def make_train_step(cfg: ModelConfig, mesh=None, opt=None,
                    dude_cfg: Optional[DuDeConfig] = None,
                    options: TrainOptions = TrainOptions(),
                    engine: Optional[DuDeEngine] = None,
                    algo: Optional[RoundAlgo] = None) -> Callable:
    """The jitted round step, on the one canonical (flat) train state:

    ``(state: FlatTrainState, batch, sm, cm) -> (state, metrics)`` — master
    params and optimizer slots stay in the engine's segment-range ``[P]``
    layout; for the DuDe family the round and the apply fuse into one
    shard_map (``engine.round_apply``, zero-collective), for any other
    ``RoundAlgo`` from the registry (``sync_sgd`` / ``mifa`` / ``fedbuff``)
    the rule's round body runs mesh-native on the same slabs and its
    ``applied`` gate holds the optimizer when the rule says so.  The only
    gather left is the single params all-gather feeding ``spec.unravel``
    for the forward.

    (The legacy pytree-tuple signature and the ``flat_optimizer=`` keyword
    shim are retired; a held tuple state converts once through
    ``flat_state_from_legacy`` — see the docs/api.md migration table.)
    """
    opt = opt or sgd(0.01)
    dude_cfg = dude_cfg or DuDeConfig(cfg.n_workers, cfg.dude_buffer_dtype)
    engine = engine or make_engine(cfg, mesh, dude_cfg, options)
    algo = algo or make_round_algo(
        "dude_accum" if engine.accumulate else "dude", engine)
    shard = make_shard_hook(mesh)

    gdt = options.grad_dtype or jnp.float32
    tp_plan = None      # TP-native exchange plan (params_layout="tp")
    if options.params_layout == "tp":
        if mesh is None or engine.mesh is None:
            raise ValueError(
                "params_layout='tp' needs a mesh-native engine (pass a mesh "
                "and keep shard_engine=True); the replicated layout is the "
                "meshless fallback")
        tp_plan = engine.tp_plan(param_shardings(abstract_params(cfg), mesh))
    flat_sh = None      # [n, P] slab sharding for the raveled grads
    leaf_sh = None      # legacy per-leaf constraint (unsharded engine)
    rs_fn = None        # explicit reduce-scatter into the owned P-shard
    if options.constrain_grads and mesh is not None and tp_plan is None:
        if engine.mesh is not None:
            flat_sh = engine.shardings().g_workers
            if "data" in engine.paxes and mesh.shape["data"] > 1:
                rs_fn = _grad_reduce_scatter(mesh, engine.paxes)
        else:
            leaf_sh = dude_state_shardings(abstract_params(cfg), mesh,
                                           dude_cfg.n_workers)["g_workers"]
    D = mesh.shape["data"] if rs_fn is not None else 1

    def per_worker_grad(params, wbatch):
        (total, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, wbatch, cfg, shard=shard), has_aux=True
        )(params)
        return grads, {k: metrics[k] for k in STEP_METRICS if k in metrics}

    def fresh_grads(params, batch):
        """Stacked backward -> [n, P] slab in the engine's grad layout.

        GSPMD's partitioner lowers "all-reduce then consume a shard" as
        all-reduce + dynamic-slice; to get a true reduce-scatter into the
        engine's P-shards, the data-axis reduction of the gradient is made
        EXPLICIT: split every worker's batch into its 'data'-axis slices
        at the vmap level (the backward then produces per-slice partial
        gradients that stay resident on their shard) and psum-scatter the
        raveled slab straight into the shard each device owns.
        """
        with jax.named_scope(spans.BACKWARD):
            split = (D > 1 and all(x.ndim >= 2 and x.shape[1] % D == 0
                                   for x in jax.tree.leaves(batch)))
            if D > 1 and not split:
                _warn_unsplittable(batch, D)
            vbatch = batch
            if split:
                vbatch = jax.tree.map(
                    lambda x: jnp.swapaxes(
                        x.reshape((x.shape[0], D, x.shape[1] // D)
                                  + x.shape[2:]), 0, 1
                    ).reshape((D * x.shape[0], x.shape[1] // D)
                              + x.shape[2:]),
                    batch)
            grads, wm = jax.vmap(per_worker_grad,
                                 in_axes=(None, 0))(params, vbatch)
        with jax.named_scope(spans.RAVEL):
            if tp_plan is not None:
                # reverse TP-native exchange: TP-layout gradient leaves ->
                # [n, P] slab shards, no replicated [n, P] intermediate (the
                # data-axis reduction lands on the TP blocks at the shard_map
                # boundary, bounded by each leaf's segment)
                fresh = engine.spec.ravel_stacked_sharded(
                    grads, mesh, dtype=gdt, plan=tp_plan)
                return fresh, wm
            if leaf_sh is not None:
                grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                     grads, leaf_sh)
            # The barrier keeps XLA from fusing the backward into the ravel's
            # concatenate: with a large-vocab embedding gradient in that fusion
            # the TPU compile of the step takes minutes instead of seconds.
            grads = jax.lax.optimization_barrier(grads)
            # ravel INSIDE the constraint: the stacked backward output lands
            # directly in the engine's slab layout instead of whatever per-leaf
            # layout GSPMD would pick for the pytree.
            fresh = engine.spec.ravel_stacked(grads, gdt)
            if split:
                # [D*n, P] partial grads, rows resident per data-shard
                fresh = jax.lax.with_sharding_constraint(
                    fresh, NamedSharding(mesh, P("data", None)))
                fresh = rs_fn(fresh)  # -> [n, P] in the engine slab sharding
            elif flat_sh is not None:
                fresh = jax.lax.with_sharding_constraint(fresh, flat_sh)
            return fresh, wm

    fopt = flat_twin(opt)
    repl_sh = None
    if mesh is not None:
        repl_sh = NamedSharding(mesh, P())

    def flat_train_step(state: FlatTrainState, batch,
                        start_mask, commit_mask):
        with jax.named_scope(spans.UNRAVEL):
            if tp_plan is not None:
                # TP-native path: each leaf's flat range is copied straight
                # out of the P-shards into its Megatron-TP layout via the
                # plan's ppermute ring — no device ever holds the full [P]
                # vector; the forward consumes the TP blocks in place.
                params = engine.spec.unravel_sharded(
                    state.params, mesh, plan=tp_plan)
            else:
                pf = state.params
                if repl_sh is not None:
                    # THE one all-gather per step: materialize the full [P]
                    # vector once; every leaf slice below is then local, and
                    # the forward consumes the leaves without further param
                    # collectives (re-sharding them per-leaf here would turn
                    # into FSDP-style per-layer re-gathers).
                    pf = jax.lax.with_sharding_constraint(pf, repl_sh)
                # slice+reshape+cast to the per-leaf target dtypes recorded in
                # the FlatSpec (f32 masters feed a bf16 forward at large scale)
                params = engine.spec.unravel(pf)
        fresh, wm = fresh_grads(params, batch)
        with jax.named_scope(spans.ROUND):
            if algo.fused_apply:
                srv_state, _, pf_new, opt_new = engine.round_apply(
                    state.engine, fresh, start_mask, commit_mask,
                    state.params, state.opt, fopt)
                applied = jnp.array(True)
            else:
                srv_state, g, applied = algo.round(
                    state.engine, fresh, start_mask, commit_mask)
                # gated flat apply: slots/params/step only advance on rounds
                # the rule actually applies (FedBuff holds until its buffer
                # fills); everything stays elementwise on the sharded [P]
                # slabs.
                t_new = state.opt.step + applied.astype(jnp.int32)
                pf_up, slots_up = fopt.update(state.params, g,
                                              state.opt.slots, t_new)
                pf_new = jnp.where(applied, pf_up, state.params)
                slots_new = jax.tree.map(
                    lambda u, o: jnp.where(applied, u, o),
                    slots_up, state.opt.slots)
                opt_new = FlatOptState(t_new, slots_new)
        metrics = {"loss": jnp.mean(wm.pop("loss")),
                   "applied": applied.astype(jnp.float32)}
        # MoE: (token, slot) pairs routed to held experts, and dropped (0),
        # over the workers and layers
        metrics.update({k: jnp.sum(v) for k, v in wm.items()})
        # indexed backend: cumulative commits/latches dropped by the static
        # index_width bound — the in-graph jax.debug warning's structured
        # twin, so drops show up in every step's metrics, not just stderr
        if getattr(srv_state, "drops", None) is not None:
            metrics["engine_drops"] = srv_state.drops.astype(jnp.float32)
        return FlatTrainState(pf_new, opt_new, srv_state), metrics

    return flat_train_step


def flat_state_from_legacy(engine: DuDeEngine, opt, params: Pytree,
                           opt_state: OptState,
                           dude_state: EngineState) -> FlatTrainState:
    """Migration helper: a legacy ``(params, opt_state, dude_state)`` tuple
    -> the canonical ``FlatTrainState`` (master params raveled to f32
    ``[P]``, per-leaf optimizer slots raveled to the flat twin's slab
    layout, engine state adopted as-is).  The pytree-tuple step that
    PRODUCED such tuples is retired — convert once with this helper, then
    continue through ``api.Trainer`` / the flat step; the full old-call ->
    new-call mapping is the migration table in docs/api.md."""
    spec = engine.spec
    state = FlatTrainState(
        spec.ravel(params, jnp.float32),
        FlatOptState(opt_state.step,
                     _slots_to_flat(spec, opt.name, opt_state.slots)),
        dude_state)
    if engine.mesh is not None:
        sh = flat_train_state_shardings(engine.spec, engine.mesh,
                                        engine.paxes, state.opt,
                                        server_like=dude_state)
        state = jax.device_put(state, sh)
    return state


def _slots_to_flat(spec, opt_name: str, slots: Pytree) -> Pytree:
    """Per-leaf optimizer slots -> the flat twin's ``[P]`` slab layout."""
    if opt_name == "sgd":
        return ()
    if opt_name == "momentum":
        return spec.ravel(slots, jnp.float32)
    if opt_name == "adamw":
        return {"m": spec.ravel(slots["m"], jnp.float32),
                "v": spec.ravel(slots["v"], jnp.float32)}
    raise ValueError(f"optimizer {opt_name!r} has no flat slot layout")


_WARNED_UNSPLITTABLE: set = set()


def _warn_unsplittable(batch, D: int) -> None:
    """One-time warning when ``constrain_grads`` configured an explicit
    reduce-scatter but the batch cannot be split by the data-axis size: the
    step silently falls back to the all-reduce + slice lowering, and users
    tuning collective traffic should know which leaf blocked the split."""
    bad = tuple(tuple(jnp.shape(x)) for x in jax.tree.leaves(batch)
                if not (jnp.ndim(x) >= 2 and jnp.shape(x)[1] % D == 0))
    key = (bad, D)
    if key in _WARNED_UNSPLITTABLE:
        return
    _WARNED_UNSPLITTABLE.add(key)
    warnings.warn(
        f"constrain_grads: batch leaf shape(s) {list(bad)} have a per-worker "
        f"batch dim not divisible by the data-axis size {D}; the explicit "
        "gradient reduce-scatter is skipped this step shape (falling back "
        "to GSPMD's all-reduce + slice lowering)",
        RuntimeWarning, stacklevel=3)


def _grad_reduce_scatter(mesh, paxes: tuple) -> Callable:
    """shard_map reducing ``[D*n, P]`` per-slice partial gradients to the
    ``[n, P]`` round input, P-axis sharded exactly like the engine slabs.

    Rows arrive grouped slice-major (``row = d*n + i``), so each data-shard
    holds one ``[n, P]`` partial sum; ``psum_scatter`` over 'data' emits the
    reduce-scatter HLO (2(D-1)/D · nP bytes — half an all-reduce) and lands
    each device's P-chunk directly; the remaining P axes of ``paxes`` are
    carved out by a local slice (their copies are identical, no traffic).
    """
    assert paxes[0] == "data"
    D = mesh.shape["data"]
    rest = paxes[1:]

    def body(gv):  # [n, P] local partial sums (this shard's batch slice)
        g = jax.lax.psum_scatter(gv, "data", scatter_dimension=1,
                                 tiled=True) / D
        if rest:
            m = math.prod(mesh.shape[a] for a in rest)
            idx = jnp.int32(0)
            for a in rest:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            w = g.shape[1] // m
            g = jax.lax.dynamic_slice_in_dim(g, idx * w, w, axis=1)
        return g

    return shard_map(body, mesh=mesh, in_specs=P("data", None),
                     out_specs=P(None, paxes), check_vma=False)


def make_prefill_step(cfg: ModelConfig, mesh=None) -> Callable:
    shard = make_shard_hook(mesh)

    def prefill_step(params, batch, caches):
        return prefill(params, batch, caches, cfg, shard=shard)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh=None, *, use_window: bool = False) -> Callable:
    shard = make_shard_hook(mesh)

    def serve_step(params, tokens, caches, index):
        return model_decode_step(params, tokens, caches, index, cfg,
                                 shard=shard, use_window=use_window)

    return serve_step


# ----------------------------------------------------- abstract state + specs

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: lm_init(key, cfg))
    # master params in f32 for <50B, bf16 at extreme scale (DESIGN.md §7)
    big = cfg.name in ("qwen1.5-110b", "kimi-k2-1t-a32b")
    dt = jnp.bfloat16 if big else jnp.float32
    return jax.tree.map(lambda s: _sds(s.shape, dt), shapes)


def abstract_train_state(cfg: ModelConfig, mesh, opt=None,
                         dude_cfg: Optional[DuDeConfig] = None,
                         options: TrainOptions = TrainOptions(),
                         engine: Optional[DuDeEngine] = None,
                         algo: Optional[RoundAlgo] = None):
    """Returns (state_shapes, state_shardings) for the train step's state:
    one ``FlatTrainState`` of ShapeDtypeStructs and its
    ``flat_train_state_shardings`` — every slab rides the engine's
    segment-range P-axis split, with the server entry shaped by the
    session's rule (an ``EngineState`` for the DuDe family, the rule's own
    slabs otherwise).  ``algo`` may be a ``RoundAlgo`` or an ``AsyncAlgo``
    — both expose ``state_shapes()``.  (The retired pytree-tuple shapes are
    gone with the pytree step; see docs/api.md.)
    """
    opt = opt or sgd(0.01)
    dude_cfg = dude_cfg or DuDeConfig(cfg.n_workers, cfg.dude_buffer_dtype)
    engine = engine or make_engine(cfg, mesh, dude_cfg, options)

    algo = algo or make_round_algo(
        "dude_accum" if engine.accumulate else "dude", engine)
    fopt = flat_twin(opt)
    pf = _sds((engine.P,), jnp.float32)
    fo_state = jax.eval_shape(fopt.init, pf)
    srv_shapes = algo.state_shapes()
    st_shapes = FlatTrainState(pf, fo_state, srv_shapes)
    st_sh = flat_train_state_shardings(engine.spec, mesh,
                                       engine.paxes or (), fo_state,
                                       server_like=srv_shapes)
    return st_shapes, st_sh


def init_flat_train_state(engine: DuDeEngine, opt, params: Pytree,
                          algo: Optional[RoundAlgo] = None
                          ) -> FlatTrainState:
    """Concrete ``FlatTrainState`` from pytree params: ravel the master
    params to the f32 ``[P]`` slab, zero-init the flat optimizer slots and
    the server state (the engine's ``EngineState`` by default, the given
    ``RoundAlgo``'s own slabs otherwise), and land everything on the
    engine's P-axis shardings when it is mesh-native."""
    fopt = flat_twin(opt)
    pf = engine.spec.ravel(params, jnp.float32)
    srv = algo.init() if algo is not None else engine.init()
    state = FlatTrainState(pf, fopt.init(pf), srv)
    if engine.mesh is not None:
        sh = flat_train_state_shardings(engine.spec, engine.mesh,
                                        engine.paxes, state.opt,
                                        server_like=srv)
        state = jax.device_put(state, sh)
    return state


def train_batch_specs(cfg: ModelConfig, mesh, shape_name: str,
                      n_workers: Optional[int] = None):
    """ShapeDtypeStructs + shardings for the worker-stacked round batch."""
    spec = INPUT_SHAPES[shape_name]
    n = n_workers or cfg.n_workers
    S, GB = spec["seq_len"], spec["global_batch"]
    assert GB % n == 0, f"batch {GB} % workers {n}"
    b = GB // n
    ts = token_shape(cfg, b, S)
    tok_shape = (n,) + ts
    lab_shape = (n, b, S) + ((cfg.num_codebooks,) if cfg.num_codebooks > 1 else ())
    shapes = {
        "tokens": _sds(tok_shape, jnp.int32),
        "labels": _sds(lab_shape, jnp.int32),
    }
    shardings = {
        "tokens": batch_sharding(mesh, worker_stacked=True, extra_dims=len(ts) - 1,
                                 shape=tok_shape),
        "labels": batch_sharding(mesh, worker_stacked=True,
                                 extra_dims=len(lab_shape) - 2,
                                 shape=lab_shape),
    }
    if cfg.frontend:
        pshape = (n, b, cfg.num_prefix_tokens, cfg.frontend_dim)
        shapes["prefix_emb"] = _sds(pshape, jnp.bfloat16)
        shardings["prefix_emb"] = batch_sharding(mesh, worker_stacked=True,
                                                 extra_dims=2, shape=pshape)
    mask_sds = _sds((n,), jnp.bool_)
    repl = NamedSharding(mesh, P())
    return (shapes, mask_sds), (shardings, repl)


def serve_specs(cfg: ModelConfig, mesh, shape_name: str):
    """ShapeDtypeStructs + shardings for prefill/decode inputs."""
    spec = INPUT_SHAPES[shape_name]
    S, B = spec["seq_len"], spec["global_batch"]
    kind = spec["kind"]
    params = abstract_params(cfg)
    p_sh = param_shardings(params, mesh)
    caches = jax.eval_shape(
        partial(init_decode_caches, cfg, B, S, dtype=jnp.bfloat16)
    )
    c_sh = cache_shardings(caches, mesh)
    if kind == "prefill":
        ts = token_shape(cfg, B, S)
        batch = {"tokens": _sds(ts, jnp.int32)}
        b_sh = {"tokens": batch_sharding(mesh, worker_stacked=False,
                                         extra_dims=len(ts) - 1, shape=ts)}
        if cfg.frontend:
            batch["prefix_emb"] = _sds(
                (B, cfg.num_prefix_tokens, cfg.frontend_dim), jnp.bfloat16
            )
            b_sh["prefix_emb"] = batch_sharding(
                mesh, worker_stacked=False, extra_dims=2,
                shape=(B, cfg.num_prefix_tokens, cfg.frontend_dim))
        return (params, batch, caches), (p_sh, b_sh, c_sh)
    # decode: one token
    tshape = (B, 1) + ((cfg.num_codebooks,) if cfg.num_codebooks > 1 else ())
    tokens = _sds(tshape, jnp.int32)
    t_sh = batch_sharding(mesh, worker_stacked=False, extra_dims=len(tshape) - 1,
                          shape=tshape)
    index = _sds((), jnp.int32)
    i_sh = NamedSharding(mesh, P())
    return (params, tokens, caches, index), (p_sh, t_sh, c_sh, i_sh)
