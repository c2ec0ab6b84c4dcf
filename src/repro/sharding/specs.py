"""Sharding rules: parameter / DuDe-state / batch / cache PartitionSpecs.

Layout (DESIGN.md §5):
  * Params: Megatron-TP over ``model`` on heads/ffn/experts/vocab dims ×
    FSDP over ``data`` on the complementary dim; replicated over ``pod``.
  * DuDe buffers (g~, G~_i, in-flight): leading worker dim — unsharded on a
    single pod, ``pod``-sharded multi-pod (pods are worker-group boundaries);
    parameter dims shard like the params (full-mesh elementwise state).
  * Round batch [n_workers, B/n, S]: worker dim ``pod``-sharded (multi-pod)
    or replicated; per-worker batch over ``data``.
  * KV caches: batch over ``data`` (+``pod``), sequence over ``model``
    (flash-decode / long-context layout; head-count agnostic).

Every rule checks divisibility against the mesh and silently drops an axis
that does not divide (replication is always correct, just more memory).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.engine import EngineState
from ..core.flatten import FlatSpec
from ..optim.transforms import FlatOptState, FlatTrainState

Pytree = Any

# param names whose rank-2 kernel is "down-like": (model, data) instead of
# (data, model) — keeps each matmul's contracting dim sharded consistently.
_DOWN_LIKE = ("wo", "down", "out_proj", "ff_down")


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh: Mesh, spec_entries, shape):
    """Drop axes that don't divide their dim."""
    out = []
    for dim, ax in zip(shape, spec_entries):
        if ax is None:
            out.append(None)
        elif dim % _axsize(mesh, ax) == 0:
            out.append(ax)
        else:
            out.append(None)
    return P(*out)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(pathstr: str, shape, mesh: Mesh, *, stacked: bool = False,
               fsdp="data") -> P:
    """PartitionSpec for one parameter leaf.

    ``stacked`` — leaf lives under stack/groups and has a leading n_groups dim.
    ``fsdp`` — axis (or axes tuple) carrying the FSDP shard of each kernel;
    multi-pod perf option M1 uses ('pod', 'data').
    """
    if stacked:
        inner = param_spec(pathstr, shape[1:], mesh, stacked=False, fsdp=fsdp)
        return P(None, *inner)

    name = pathstr.rsplit("/", 1)[-1]
    parent = pathstr.split("/")[-2] if "/" in pathstr else ""
    rank = len(shape)

    if name == "embedding":  # [V, d]
        return _fit(mesh, ("model", fsdp), shape)
    if name in ("wup", "wgate"):  # MoE experts [E, d, f]
        return _fit(mesh, ("model", fsdp, None), shape)
    if name == "wdown":  # [E, f, d]
        return _fit(mesh, ("model", None, fsdp), shape)
    if name == "conv":  # [W, C] depthwise conv kernels
        return _fit(mesh, (None, "model"), shape)
    if name in ("ri", "rf", "rz", "ro") or (
        name in ("wq", "wk", "wv") and rank == 3
    ):  # block-diagonal per-head weights [H, hd, hd] (sLSTM rec, mLSTM qkv)
        return _fit(mesh, (None, None, "model"), shape)
    if name == "kernel":
        if rank != 2:
            return P(*([None] * rank))
        if any(d in pathstr for d in _DOWN_LIKE):
            return _fit(mesh, ("model", fsdp), shape)
        return _fit(mesh, (fsdp, "model"), shape)
    if name == "bias" and rank == 1:
        if any(d in pathstr for d in _DOWN_LIKE):
            return _fit(mesh, (fsdp,), shape)
        return _fit(mesh, ("model",), shape)
    # norms, gates, A_log, D, dt_bias, conv_bias, scales: replicate
    return P(*([None] * rank))


def _is_stacked(pathstr: str) -> bool:
    """Leaf lives under a stacked layer-group (leading n_layers dim).  The
    model's param tree has ``groups`` at the ROOT ("groups/0/attn/..."), so
    a bare substring test for "/groups/" misses it — and a prefixed tree
    (e.g. AdamW slots under "m/...") would disagree with the params."""
    return pathstr.startswith("groups/") or "/groups/" in pathstr


def param_shardings(params: Pytree, mesh: Mesh, *, pod_fsdp: bool = False) -> Pytree:
    fsdp = ("pod", "data") if (pod_fsdp and "pod" in mesh.shape) else "data"
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        out.append(NamedSharding(
            mesh, param_spec(ps, leaf.shape, mesh, stacked=_is_stacked(ps),
                             fsdp=fsdp)))
    return jax.tree_util.tree_unflatten(treedef, out)


def slot_shardings(params: Pytree, slots: Pytree, mesh: Mesh) -> Pytree:
    """Shardings for pytree optimizer slots: every slot leaf shards exactly
    like its parameter.

    Slot trees are params-shaped (momentum ``m``) or a dict of params-shaped
    trees (AdamW ``{"m": ..., "v": ...}``).  Running ``param_shardings``
    directly on the latter would prefix every path with ``m/``/``v/`` and
    leave the name-pattern rules one component off, so slot subtrees that
    structurally match ``params`` reuse the param shardings verbatim —
    mismatch is impossible by construction (asserted per optimizer in
    ``tests/test_flat_state.py``)."""
    p_struct = jax.tree_util.tree_structure(params)
    p_sh = param_shardings(params, mesh)
    if jax.tree_util.tree_structure(slots) == p_struct:
        return p_sh
    if isinstance(slots, dict) and slots and all(
            jax.tree_util.tree_structure(v) == p_struct
            for v in slots.values()):
        return {k: p_sh for k in slots}
    return param_shardings(slots, mesh)


def dude_state_shardings(params: Pytree, mesh: Mesh, n_workers: int) -> dict:
    """Shardings for DuDeState: g_bar like params, stacked buffers with a
    leading worker dim (pod-sharded when divisible)."""
    multi_pod = "pod" in mesh.shape
    worker_ax = "pod" if (multi_pod and n_workers % mesh.shape["pod"] == 0) else None

    def one(path, leaf, extra_axis):
        ps = _path_str(path)
        inner = param_spec(ps, leaf.shape, mesh, stacked=_is_stacked(ps))
        if extra_axis is False:
            return NamedSharding(mesh, inner)
        return NamedSharding(mesh, P(worker_ax, *inner))

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    gbar = jax.tree_util.tree_unflatten(
        treedef, [one(p, l, False) for p, l in flat]
    )
    buf = jax.tree_util.tree_unflatten(
        treedef, [one(p, l, True) for p, l in flat]
    )
    scalar = NamedSharding(mesh, P())
    vec = NamedSharding(mesh, P())
    return {
        "g_bar": gbar, "g_workers": buf, "inflight": buf,
        "acc_count": vec, "step": scalar,
    }


def engine_state_shardings(spec: FlatSpec, mesh: Mesh, axes: Any = None,
                           like: Any = None) -> EngineState:
    """NamedShardings for the flat ``EngineState`` of a ServerEngine.

    The P axis is split into the contiguous segment ranges of the spec's
    shard table (``FlatSpec.shard_ranges``): ``g_bar`` is ``P(axes)``, the
    ``[n, P]`` slabs are ``P(None, axes)`` (worker axis replicated — workers
    are rows, P-shards are columns), ``acc_count``/``step`` replicated.

    ``axes`` — mesh axis name(s) carrying the P shard; None = all mesh axes.
    Following the module's convention, an axis product that does not divide
    ``spec.padded_size`` drops to replication (build the spec with
    ``make_flat_spec(tree, mesh_axis_size=k)`` to guarantee divisibility).

    ``like`` — an ``EngineState`` of arrays/ShapeDtypeStructs whose
    None-ness the result mirrors.  Compressed commit formats
    (``core/compression.py``) populate the trailing slots: the ``[n, P/128]``
    scale slabs shard their trailing dim like the ``[n, P]`` rows (tile
    boundaries align with shard boundaries because ``P/k`` is a multiple of
    128) and the ``[P]`` EF residual shards like ``g_bar``.  Sparse-transport
    engines (``sparse_meta``) add the ``[n, P/128]`` touched-tile bitmaps —
    sharded exactly like the scale slabs, so every P-shard owns the metadata
    of its own tiles — and the indexed backend adds the replicated scalar
    ``drops`` counter.  With ``like`` omitted (or an f32 state) those fields
    stay ``None``, preserving the historical 5-field structure exactly.
    """
    if axes is None:
        axes = tuple(mesh.axis_names)
    elif isinstance(axes, str):
        axes = (axes,)
    axes = tuple(axes)
    k = _axsize(mesh, axes)
    if not axes or k <= 1 or spec.padded_size % k != 0:
        vec, row = P(), P()
    else:
        vec, row = P(axes), P(None, axes)
    compressed = like is not None and like.ef is not None
    has = lambda f: like is not None and getattr(like, f, None) is not None
    return EngineState(
        g_bar=NamedSharding(mesh, vec),
        g_workers=NamedSharding(mesh, row),
        inflight=NamedSharding(mesh, row),
        acc_count=NamedSharding(mesh, P()),
        step=NamedSharding(mesh, P()),
        gw_scale=NamedSharding(mesh, row) if compressed else None,
        infl_scale=NamedSharding(mesh, row) if compressed else None,
        ef=NamedSharding(mesh, vec) if compressed else None,
        gw_touched=NamedSharding(mesh, row) if has("gw_touched") else None,
        in_touched=NamedSharding(mesh, row) if has("in_touched") else None,
        drops=NamedSharding(mesh, P()) if has("drops") else None,
    )


def flat_vec_sharding(spec: FlatSpec, mesh: Mesh, axes: Any = None
                      ) -> NamedSharding:
    """The NamedSharding of ONE flat ``[P]`` slab (the ``g_bar`` rule):
    segment-range P-axis split over ``axes`` (None = all mesh axes),
    dropping to replication when the axis product does not divide ``P``.
    Used by the async runtime to land per-arrival raveled gradients and
    worker param snapshots directly in the engine's layout.  A single-leaf
    view of the structural ``flat_slab_shardings`` rule, so the fallback
    logic exists once."""
    return flat_slab_shardings(
        jax.ShapeDtypeStruct((spec.padded_size,), jnp.float32),
        spec, mesh, axes)


def flat_slab_shardings(state_like: Pytree, spec: FlatSpec, mesh: Mesh,
                        axes: Any = None) -> Pytree:
    """Structural P-axis shardings for ANY pytree of flat slabs: every leaf
    whose trailing dim equals ``spec.padded_size`` shards on that dim by the
    spec's segment ranges (``[P]`` like ``g_bar``, ``[n, P]`` like the worker
    slabs); everything else (counters, masks) replicates.  This is how the
    server state of a non-DuDe ``RoundAlgo`` (MIFA memory, FedBuff
    accumulator) rides the engine's layout inside one ``FlatTrainState``."""
    if axes is None:
        axes = tuple(mesh.axis_names)
    elif isinstance(axes, str):
        axes = (axes,)
    axes = tuple(axes)
    k = _axsize(mesh, axes)
    sharded = axes and k > 1 and spec.padded_size % k == 0

    def one(leaf):
        shape = tuple(jnp.shape(leaf))
        if sharded and shape and shape[-1] == spec.padded_size:
            return NamedSharding(mesh, P(*((None,) * (len(shape) - 1)
                                           + (axes,))))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, state_like)


def flat_train_state_shardings(spec: FlatSpec, mesh: Mesh, axes: Any = None,
                               opt_state_like: Any = None,
                               server_like: Any = None) -> FlatTrainState:
    """NamedShardings for a ``FlatTrainState`` on ``mesh``.

    Everything rides the engine's segment-range P-axis split: the ``[P]``
    master params and every ``[P]`` optimizer slot slab shard like ``g_bar``
    (``P(axes)``), the step counter is replicated, and the server state uses
    ``engine_state_shardings`` (``server_like`` None or an ``EngineState`` —
    the DuDe family) or the structural ``flat_slab_shardings`` rule (any
    other ``RoundAlgo`` state).  ``opt_state_like`` supplies the slot tree
    structure (arrays or ShapeDtypeStructs; ``None`` means no slots)."""
    if server_like is None or isinstance(server_like, EngineState):
        srv_sh = engine_state_shardings(spec, mesh, axes, like=server_like)
        vec = srv_sh.g_bar
    else:
        srv_sh = flat_slab_shardings(server_like, spec, mesh, axes)
        vec = flat_slab_shardings(jax.ShapeDtypeStruct((spec.padded_size,),
                                                       jnp.float32),
                                  spec, mesh, axes)
    repl = NamedSharding(mesh, P())
    slots = opt_state_like.slots if opt_state_like is not None else ()
    return FlatTrainState(
        params=vec,
        opt=FlatOptState(step=repl,
                         slots=jax.tree.map(lambda _: vec, slots)),
        engine=srv_sh,
    )


# ------------------------------------------------- TP-native unravel plan

@dataclasses.dataclass(frozen=True)
class LeafExchange:
    """Static exchange recipe for ONE leaf of a TP-native unravel.

    ``entries`` is the leaf's resolved Megatron-TP PartitionSpec, one entry
    per dim: ``None`` (replicated dim) or a tuple of mesh axis names.
    ``block_shape`` is the per-device TP block (``shape[d] / prod(entries[d])``
    per dim) and ``strides`` the row-major element strides of the FULL leaf —
    together they place every block element at its global flat offset.
    ``segments`` is the per-(shard, leaf) table from ``FlatSpec
    .shard_segments``: which P-shards hold a piece of this leaf, in
    leaf-local coordinates — the bound on what any exchange for this leaf
    may touch."""

    index: int
    offset: int
    size: int
    shape: tuple
    dtype: Any
    entries: tuple        # per-dim: None | tuple of mesh axis names
    block_shape: tuple
    strides: tuple
    segments: tuple       # ((shard, leaf_lo, leaf_hi), ...)

    @property
    def block_size(self) -> int:
        return int(np.prod(self.block_shape, dtype=np.int64))

    @property
    def tp_axes(self) -> tuple:
        """Mesh axes this leaf's layout actually uses (replicated over the
        rest of the P-axis group)."""
        out = []
        for e in self.entries:
            if e is not None:
                out.extend(e)
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class FlatTpPlan:
    """Static per-(shard, leaf) exchange plan: flat P-shards <-> TP blocks.

    Consumed by ``FlatSpec.unravel_sharded`` / ``ravel_stacked_sharded``
    (core/flatten.py): the flat vector stays split into its ``k`` contiguous
    segment-range windows of ``window`` elements, one per device of the
    P-axis group ``axes``; the windows circulate around a ppermute ring and
    each device copies exactly its TP-block elements out of (into) each
    passing window.  No collective ever carries more than one ``[window]``
    buffer, and no device materializes the full ``[P]`` vector or a full
    leaf.  Built by ``flat_to_tp_plan`` and cached per (spec, mesh, axes,
    leaf specs)."""

    axes: tuple           # P-axis mesh axes, shard-linear (major -> minor)
    mesh_shape: tuple     # sizes of those axes
    k: int                # number of P-shards == ring length
    window: int           # elements per P-shard (spec.padded_size / k)
    leaves: tuple         # LeafExchange per spec leaf
    needs_i64: bool       # flat offsets exceed int32 (>2 GiB of elements);
                          # informational — the rings address windows in two
                          # int32 digits (pos>>7, pos&127) at every scale, so
                          # no int64 ever enters the traced index math

    # ------------------------------------------------ analytics (for the
    # ------------------------------------------------ bench and the docs)

    @property
    def full_vector_bytes(self) -> int:
        """Per-device bytes of the replicated-path [P] f32 materialization."""
        return 4 * self.window * self.k

    @property
    def window_bytes(self) -> int:
        return 4 * self.window

    @property
    def block_bytes(self) -> int:
        """Per-device bytes of all TP blocks in f32 staging."""
        return sum(4 * lf.block_size for lf in self.leaves)

    @property
    def index_bytes(self) -> int:
        """Per-device bytes of the gather-position digit vectors (hi + lo,
        both int32, at every scale)."""
        return sum(8 * lf.block_size for lf in self.leaves)

    @property
    def peak_bytes(self) -> int:
        """Per-device peak live bytes of a TP-native unravel: own window +
        one circulating window + every TP block (f32) + position vectors.
        The replicated path peaks at ``full_vector_bytes`` instead."""
        return 2 * self.window_bytes + self.block_bytes + self.index_bytes

    @property
    def ring_bytes(self) -> int:
        """Per-device bytes moved by the ring (k-1 window hops)."""
        return (self.k - 1) * self.window_bytes

    def max_leaf_segment_bytes(self) -> int:
        """f32 bytes of the largest per-(shard, leaf) segment — the bound on
        any single leaf's per-window gather."""
        best = 0
        for lf in self.leaves:
            for _, a, b in lf.segments:
                best = max(best, 4 * (b - a))
        return best


_TP_PLAN_CACHE: dict = {}


def _leaf_pspec_entries(sh, rank: int) -> tuple:
    """NamedSharding | PartitionSpec -> per-dim entries, padded to rank."""
    ps = sh.spec if isinstance(sh, NamedSharding) else sh
    entries = list(tuple(ps)) + [None] * (rank - len(tuple(ps)))
    out = []
    for e in entries[:rank]:
        if e is None:
            out.append(None)
        elif isinstance(e, str):
            out.append((e,))
        else:
            out.append(tuple(e))
    return tuple(out)


def flat_to_tp_plan(spec: FlatSpec, mesh: Mesh, param_sh: Pytree,
                    axes: Any = None) -> FlatTpPlan:
    """The TP-native unravel rule: a static exchange plan mapping the flat
    vector's segment-range P-shards to the params' Megatron-TP layout.

    ``param_sh`` is the ``param_shardings`` pytree (NamedShardings or raw
    PartitionSpecs) for the SAME tree layout as ``spec``; ``axes`` the mesh
    axes carrying the P shard (None = all mesh axes, 'data' leading, i.e.
    the engine's ``paxes`` convention).  Every leaf spec must (a) only use
    axes from the P-axis group — the exchange redistributes within that
    group — and (b) divide its dims; a non-dividing axis drops to
    replication (the module-wide ``_fit`` convention).

    The plan is static: per leaf it records the TP block shape, the full
    leaf's element strides, and the per-(shard, leaf) segment table from
    ``FlatSpec.shard_segments`` — everything ``unravel_sharded`` needs to
    copy block elements straight out of the circulating windows.  Cached on
    (spec, mesh, axes, leaf specs)."""
    if axes is None:
        axes = tuple(sorted(mesh.axis_names, key=lambda a: (a != "data",)))
    elif isinstance(axes, str):
        axes = (axes,)
    axes = tuple(axes)
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(f"axis {a!r} not in mesh {tuple(mesh.axis_names)}")
    k = _axsize(mesh, axes)
    if k < 1 or spec.padded_size % k != 0:
        raise ValueError(
            f"P={spec.padded_size} not divisible into {k} shards over "
            f"axes {axes}; build the spec with mesh_axis_size={k}")

    sh_leaves = spec.treedef.flatten_up_to(param_sh)
    if len(sh_leaves) != len(spec.shapes):
        raise ValueError(
            f"param_sh has {len(sh_leaves)} leaves, spec has "
            f"{len(spec.shapes)}")
    entries_key = tuple(_leaf_pspec_entries(sh, len(shp))
                        for sh, shp in zip(sh_leaves, spec.shapes))
    key = (spec, mesh, axes, entries_key)
    plan = _TP_PLAN_CACHE.get(key)
    if plan is not None:
        return plan

    window = spec.padded_size // k
    # per-leaf segment tables: invert the per-shard tables (uses the
    # memoized FlatSpec.shard_segments when the shard counts agree)
    per_leaf_segs: dict = {i: [] for i in range(len(spec.shapes))}
    if k == spec.mesh_axis_size:
        for s in range(k):
            for i, a, b in spec.shard_segments(s):
                per_leaf_segs[i].append((s, a, b))
    else:
        for s in range(k):
            lo, hi = s * window, (s + 1) * window
            for i, (off, sz) in enumerate(zip(spec.offsets, spec.sizes)):
                a, b = max(lo, off), min(hi, off + sz)
                if a < b:
                    per_leaf_segs[i].append((s, a - off, b - off))

    leaves = []
    for i, (shp, ents) in enumerate(zip(spec.shapes, entries_key)):
        fitted = []
        bshp = []
        for d, e in zip(shp, ents):
            if e is not None:
                bad = [a for a in e if a not in axes]
                if bad:
                    raise ValueError(
                        f"leaf {i} spec uses axes {bad} outside the P-axis "
                        f"group {axes}")
            m = _axsize(mesh, e)
            if e is None or d % m != 0:
                fitted.append(None)
                bshp.append(d)
            else:
                fitted.append(e)
                bshp.append(d // m)
        strides = []
        s = 1
        for d in reversed(shp):
            strides.insert(0, s)
            s *= int(d)
        leaves.append(LeafExchange(
            index=i, offset=spec.offsets[i], size=spec.sizes[i], shape=shp,
            dtype=spec.dtypes[i], entries=tuple(fitted),
            block_shape=tuple(bshp), strides=tuple(strides),
            segments=tuple(per_leaf_segs[i])))

    if window % 128:
        raise ValueError(
            f"TP-native exchange needs 128-lane-aligned windows; got "
            f"window={window} (pad the spec with pad_multiple=128)")
    if spec.padded_size > (np.iinfo(np.int32).max << 7):
        raise NotImplementedError(
            f"padded_size={spec.padded_size} exceeds 2^38: the two-digit "
            f"int32 window addressing (128 lanes per row) tops out at "
            f"~274 B params")
    plan = FlatTpPlan(
        axes=axes, mesh_shape=tuple(mesh.shape[a] for a in axes), k=k,
        window=window, leaves=tuple(leaves),
        needs_i64=spec.padded_size > np.iinfo(np.int32).max)
    _TP_PLAN_CACHE[key] = plan
    return plan


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def batch_sharding(mesh: Mesh, *, worker_stacked: bool, extra_dims: int = 1,
                   shape=None):
    """Sharding for token batches.

    worker_stacked: [n_workers, B/n, S?] — worker dim over 'pod' (if present),
    per-worker batch over 'data'.  Otherwise [B, ...] over all dp axes.
    Axes that do not divide their dim (e.g. batch=1 at long_500k) are dropped.
    """
    if worker_stacked:
        wax = "pod" if "pod" in mesh.shape else None
        spec = (wax, "data") + (None,) * extra_dims
    else:
        dp = dp_axes(mesh)
        # try the full dp product; fall back to 'data' alone; else replicate
        if shape is not None and shape[0] % _axsize(mesh, dp) != 0:
            dp = "data" if shape[0] % _axsize(mesh, "data") == 0 else None
        spec = (dp,) + (None,) * extra_dims
    if shape is not None:
        fitted = []
        for dim, ax in zip(shape, spec):
            fitted.append(ax if (ax is None or dim % _axsize(mesh, ax) == 0) else None)
        spec = tuple(fitted) + spec[len(shape):]
    return NamedSharding(mesh, P(*spec))


def cache_shardings(caches: Pytree, mesh: Mesh) -> Pytree:
    """KV caches [(G,) B, S, K, hd] — batch over dp, sequence over model.
    SSM states [(G,) B, H, ...] — batch over dp, heads over model."""
    dp = dp_axes(mesh)

    def one(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        stacked = ps.startswith("groups/") or "/groups/" in ps
        lead = (None,) if stacked else ()
        body = shape[1:] if stacked else shape
        name = ps.rsplit("/", 1)[-1]
        if name in ("k", "v") and len(body) == 4:  # [B, S, K, hd]
            ent = (dp, "model", None, None)
        elif name == "ssm" and len(body) == 4:  # [B, H, P, N]
            ent = (dp, "model", None, None)
        elif name == "C" and len(body) == 4:  # mLSTM [B, H, hd, hd]
            if body[1] % _axsize(mesh, "model") == 0:
                ent = (dp, "model", None, None)
            else:  # few big heads: shard the matrix-memory rows instead
                ent = (dp, None, "model", None)
        elif name == "conv" and len(body) == 3:  # [B, W-1, C]
            ent = (dp, None, "model")
        elif len(body) >= 2:
            ent = (dp,) + (None,) * (len(body) - 1)
        elif len(body) == 1:
            ent = (dp,)
        else:
            ent = ()
        # divisibility fit on the body
        fitted = []
        for dim, ax in zip(body, ent):
            fitted.append(ax if dim % _axsize(mesh, ax) == 0 else None)
        return NamedSharding(mesh, P(*(lead + tuple(fitted))))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def make_shard_hook(mesh: Optional[Mesh]):
    """Activation sharding-constraint hook passed into the model."""
    if mesh is None:
        return lambda x, name: x
    dp = dp_axes(mesh)
    specs = {
        "act_resid": lambda s: P(dp, *([None] * (len(s) - 1))),
        "act_heads": lambda s: P(dp, None, "model", None),
        "act_kv": lambda s: P(dp, None, "model" if s[2] % _axsize(mesh, "model") == 0 else None, None),
        "logits": lambda s: P(dp, *([None] * (len(s) - 2)), "model"),
    }

    def hook(x, name):
        fn = specs.get(name)
        if fn is None:
            return x
        spec = fn(x.shape)
        fitted = []
        for dim, ax in zip(x.shape, spec):
            fitted.append(ax if dim % _axsize(mesh, ax) == 0 else None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fitted)))

    hook.mesh = mesh    # the model reads where its activations live
    return hook
