"""ServerEngine: the DuDe server iteration on one flat buffer layout.

Every server-side algorithm in this repo ultimately streams over Theta(n * p)
buffer state.  ``DuDeEngine`` owns that state in ONE canonical layout —
``g_bar`` as a padded flat ``[P]`` f32 vector, ``g_workers``/``inflight`` as
``[n, P]`` slabs in the configured buffer dtype — and exposes the two paper
entry points (``commit`` for the fully-async mode, ``round`` for the
semi-async SPMD mode), plus ``round_apply`` — the round fused with a flat
optimizer apply on ``[P]`` master params and slot slabs (the flat-state
training path) — over three interchangeable backends:

* ``"reference"`` — masked jnp sweep over all n rows; the paper-faithful
  oracle (identical math to the historical ``dude_round``), and the only
  backend supporting the beyond-paper ``accumulate`` variant.
* ``"indexed"``   — gather/scatter touching only the selected rows.  The
  traffic saving (~4kP instead of ~4nP bytes per round) requires a static
  bound k on the active set: set ``index_width`` (the schedule usually
  knows max |C_t|), or use ``round_indexed`` with host-narrowed arrays.
  With the default width n the mask path is correct but saves nothing.
* ``"pallas"``    — the fused TPU kernel (``kernels/dude_update.py``): one
  pass over all five streams, optionally folding the SGD parameter update
  into the same pass.  Runs under ``interpret=True`` on CPU.

Backends agree bit-for-bit on ``g_bar`` (all accumulate the commit delta in
f32) and on the buffers up to the shared buffer-dtype rounding; the
equivalence is enforced by ``tests/test_engine.py``.

Mesh-native mode: give the engine ``(mesh, axis_name)`` and every entry
point runs under ``shard_map`` with the P axis split into the contiguous
segment ranges of the spec's shard table (``FlatSpec.shard_ranges``) —
``g_bar`` as ``P(axis)``, the ``[n, P]`` slabs as ``P(None, axis)``, masks
and scalars replicated.  The round is elementwise on P (the worker-axis sum
is local to each P-shard), so a sharded round moves ZERO bytes across
devices; the fused Pallas backend runs per shard with the tile that
``DuDeEngine.tile`` derives from the shard's VMEM footprint.  The spec
must be built shard-aligned: ``make_flat_spec(tree, mesh_axis_size=k)``
with ``k`` the product of the chosen mesh axes.  Sharded and unsharded
engines agree bit-for-bit on ``g_bar`` (``tests/test_engine_sharded.py``).

``core/dude.py`` re-exports the historical pytree API (``dude_commit`` /
``dude_round`` / ``dude_round_indexed``) as thin ravel->engine->unravel
wrappers, so callers keep pytree ergonomics while the hot loop runs on flat
slabs.

Documented in docs/engine.md — "Backends", "Sharding the flat layout" and
"Flat training state" (``round_apply``); ``commit`` is the per-arrival hot
path of the async runtime (docs/async.md, "Arrival-granularity
algorithms").
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import checkify
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from .compression import (
    COMMIT_FORMATS, CommitCodec, SparseRow, touched_tiles,
)
from .flatten import FlatSpec, make_flat_spec
from ..kernels.dude_update import (
    SLOT_STREAMS, derive_tile, dude_round_apply_pallas,
    dude_round_apply_q_pallas, dude_round_apply_sparse_pallas,
    dude_update_pallas,
)
from ..optim.transforms import FlatOptState, FlatOptimizer

Pytree = Any

__all__ = ["BACKENDS", "EngineState", "DuDeEngine", "masks_to_indices_jnp"]

BACKENDS = ("reference", "indexed", "pallas")

INDEX_CHECKS = ("debug", "checkify", "off")


class EngineState(NamedTuple):
    """Flat DuDe server state.  Field names mirror ``DuDeState``.

    The trailing three fields exist only under a compressed
    ``commit_format`` (``int8_ef`` / ``topk_ef``): the slabs then hold int8
    payloads, ``gw_scale``/``infl_scale`` hold their per-128-lane-tile f32
    scales, and ``ef`` carries the commit-stream error-feedback residual.
    Under ``"f32"`` they stay ``None`` — ``None`` leaves vanish from jax
    pytrees, so the f32 state keeps the exact historical flatten structure,
    checkpoint paths, and shardings (bit-for-bit compatibility).
    """

    g_bar: jnp.ndarray      # [P] f32 running aggregated gradient (paper g~)
    g_workers: jnp.ndarray  # [n, P] latest committed gradient per worker
    inflight: jnp.ndarray   # [n, P] gradient latched at job start
    acc_count: jnp.ndarray  # [n] i32 rounds accumulated (accumulate mode)
    step: jnp.ndarray       # scalar i32 server iteration counter
    gw_scale: Any = None    # [n, P/128] f32 scales of g_workers (compressed)
    infl_scale: Any = None  # [n, P/128] f32 scales of inflight (compressed)
    ef: Any = None          # [P] f32 commit-stream EF residual (compressed)
    # sparse_meta engines (topk_ef + SparseRow transport) additionally track
    # which 128-lane tiles of each slab row hold any nonzero payload — the
    # invariant "bitmap == touched_tiles(q row)" holds after every entry
    # point, so sparse commits/rounds may skip the untouched tiles exactly.
    gw_touched: Any = None  # [n, P/128] int8 touched-tile bitmap, g_workers
    in_touched: Any = None  # [n, P/128] int8 touched-tile bitmap, inflight
    # indexed backend: running count of commits/latches dropped because a
    # round's active set exceeded index_width (satellite of index_check;
    # surfaced in Trainer.step metrics as "engine_drops").
    drops: Any = None       # [] i32


def masks_to_indices_jnp(mask: jnp.ndarray, n: int) -> jnp.ndarray:
    """Traced bool mask [n] -> fixed-width [n] index array padded with n.

    Valid indices sort to the front; entries == n are dropped by the
    scatter's ``mode="drop"``.  Shape-static, so usable under jit (unlike
    host-side ``masks_to_indices``).
    """
    return jnp.sort(jnp.where(mask, jnp.arange(n, dtype=jnp.int32),
                              jnp.int32(n)))


@dataclasses.dataclass(frozen=True)
class DuDeEngine:
    """One DuDe server, one flat state layout, pluggable update backends."""

    spec: FlatSpec
    n_workers: int
    buffer_dtype: Any = jnp.float32
    accumulate: bool = False
    backend: str = "reference"
    interpret: Optional[bool] = None  # pallas only; None = auto (off on TPU)
    # indexed backend: static width of the in-graph index arrays built from
    # masks.  Must bound the max number of simultaneously starting/committing
    # workers — excess valid indices are dropped (valid indices sort first,
    # so the bound is on |C_t|, not on n).  None = n (always correct, but the
    # gather/scatter then touches all n rows and saves no traffic).  Overflow
    # is detected per round according to ``index_check``.
    index_width: Optional[int] = None
    # "debug"    — jax.debug.print a warning from inside the jitted round
    #              whenever a mask round has more active workers than
    #              index_width (commits silently dropped otherwise);
    # "checkify" — checkify.check instead: wrap the round with
    #              jax.experimental.checkify.checkify to surface the error
    #              as a real exception;
    # "off"      — no check (the seed's silent-drop behavior).
    index_check: str = "debug"
    # Mesh-native mode: run every entry point under shard_map with the P
    # axis sharded over ``axis_name`` (a mesh axis name or tuple of names;
    # None = all axes of ``mesh``).  Requires a shard-aligned spec:
    # make_flat_spec(tree, mesh_axis_size=<product of those axes>).
    mesh: Optional[Mesh] = None
    axis_name: Any = None
    # Slab storage / commit wire format (core/compression.py).  "f32" is the
    # historical full-precision layout; "int8_ef" / "topk_ef" store the
    # [n, P] slabs as int8 payloads + per-128-lane-tile f32 scale slabs and
    # add a [P] error-feedback residual on the commit stream.  The configured
    # buffer_dtype only applies to the f32 format.
    commit_format: str = "f32"
    # Sparse commit transport (topk_ef only): EngineState carries per-row
    # touched-tile bitmaps, commits may arrive as index-carrying SparseRows
    # scatter-decoded straight into the slab (commit_sparse /
    # encode_sparse_commit + sparse_fold), and the round backends fold only
    # the touched tiles of the committed rows into g_bar.  sparse_cap bounds
    # the static touched-tile slots of a SparseRow commit (None = all tiles
    # — always correct; smaller caps bound the wire bytes, overflow re-enters
    # through error feedback).  docs/engine.md "Sparse commit transport".
    sparse_meta: bool = False
    sparse_cap: Optional[int] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; options: {BACKENDS}")
        if self.accumulate and self.backend != "reference":
            raise ValueError(
                "accumulate mode is only implemented by the reference "
                f"backend, not {self.backend!r}")
        if self.commit_format not in COMMIT_FORMATS:
            raise ValueError(
                f"unknown commit_format {self.commit_format!r}; "
                f"options: {COMMIT_FORMATS}")
        if self.accumulate and self.commit_format != "f32":
            raise ValueError(
                "accumulate mode re-averages the in-flight rows every round "
                "and cannot keep quantized slabs exact; it requires "
                "commit_format='f32'")
        if self.index_width is not None and not (
                1 <= self.index_width <= self.n_workers):
            raise ValueError(
                f"index_width={self.index_width} outside [1, n_workers]")
        if self.index_check not in INDEX_CHECKS:
            raise ValueError(
                f"unknown index_check {self.index_check!r}; "
                f"options: {INDEX_CHECKS}")
        if self.sparse_meta and self.commit_format != "topk_ef":
            raise ValueError(
                "sparse_meta (SparseRow commit transport) requires "
                f"commit_format='topk_ef', not {self.commit_format!r}")
        if self.sparse_cap is not None:
            if not self.sparse_meta:
                raise ValueError("sparse_cap requires sparse_meta=True")
            if not 1 <= self.sparse_cap <= self.n_tiles:
                raise ValueError(
                    f"sparse_cap={self.sparse_cap} outside "
                    f"[1, {self.n_tiles}]")
        if self.mesh is not None:
            missing = [a for a in self.paxes if a not in self.mesh.shape]
            if missing:
                raise ValueError(
                    f"axis_name {missing} not in mesh axes "
                    f"{tuple(self.mesh.axis_names)}")
            k = self.axis_size
            if self.P % k != 0:
                raise ValueError(
                    f"P={self.P} not divisible by the {k}-way P-axis mesh; "
                    f"build the spec with make_flat_spec(tree, "
                    f"mesh_axis_size={k})")

    @classmethod
    def for_tree(cls, grad_like: Pytree, n_workers: int, **kw) -> "DuDeEngine":
        """Engine whose flat layout matches ``grad_like``'s pytree layout."""
        mesh = kw.get("mesh")
        k = 1
        if mesh is not None:
            axes = kw.get("axis_name") or tuple(mesh.axis_names)
            if isinstance(axes, str):
                axes = (axes,)
            for a in axes:
                k *= mesh.shape[a]
        return cls(spec=make_flat_spec(grad_like, mesh_axis_size=k),
                   n_workers=n_workers, **kw)

    # ---------------------------------------------------------- properties

    @property
    def P(self) -> int:
        return self.spec.padded_size

    @property
    def codec(self) -> CommitCodec:
        return CommitCodec(format=self.commit_format)

    @property
    def compressed(self) -> bool:
        return self.commit_format != "f32"

    @property
    def n_tiles(self) -> int:
        """Scale tiles per row (P / 128; the scale-slab trailing dim)."""
        return self.codec.n_tiles(self.P)

    @property
    def cap_tiles(self) -> int:
        """Static touched-tile capacity of one ``SparseRow`` commit
        (``sparse_cap``, defaulting to all tiles)."""
        return self.codec.sparse_cap(self.P, self.sparse_cap)

    @property
    def paxes(self) -> tuple:
        """Mesh axis names carrying the P shard (empty when unsharded)."""
        if self.mesh is None:
            return ()
        if self.axis_name is None:
            return tuple(self.mesh.axis_names)
        if isinstance(self.axis_name, str):
            return (self.axis_name,)
        return tuple(self.axis_name)

    @property
    def axis_size(self) -> int:
        """Number of P-axis shards (1 when unsharded)."""
        k = 1
        for a in self.paxes:
            k *= self.mesh.shape[a]
        return k

    @property
    def shard_P(self) -> int:
        """Per-device slice of the P axis (== P when unsharded)."""
        return self.P // self.axis_size

    @property
    def tile(self) -> int:
        """Lanes per grid step of the fused kernels (one place decides).

        Interpret mode evaluates one Python kernel body per grid step, so
        it collapses to a single ``[n, P/k]`` program.  On hardware the tile
        is the widest one whose double-buffered blocks and temporaries fit
        the kernels' VMEM budget, from n, the slab dtype and the stream
        count (sized for AdamW's two slot streams, so one tile serves every
        optimizer); the last block may be ragged."""
        if self._interpret():
            return self.shard_P
        slab = 1 if self.compressed else jnp.dtype(self.buffer_dtype).itemsize
        return derive_tile(self.shard_P, self.n_workers, slab,
                           max(SLOT_STREAMS.values()), self.compressed)

    def _interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    # ----------------------------------------------------------- sharding

    def shardings(self) -> EngineState:
        """NamedShardings for ``EngineState`` on this engine's mesh."""
        if self.mesh is None:
            raise ValueError("engine has no mesh")
        from ..sharding.specs import engine_state_shardings
        return engine_state_shardings(self.spec, self.mesh, self.paxes,
                                      like=self.state_shapes())

    def tp_plan(self, param_sh: Pytree):
        """The TP-native exchange plan between this engine's P-shards and
        the given Megatron-TP param shardings (``flat_to_tp_plan`` on the
        engine's mesh and P-axis group; cached).  Feed it to
        ``spec.unravel_sharded`` / ``spec.ravel_stacked_sharded`` so the
        train step never materializes the full ``[P]`` vector."""
        if self.mesh is None:
            raise ValueError("engine has no mesh")
        return self.spec.tp_plan(self.mesh, param_sh, axes=self.paxes)

    def _pspecs(self):
        """(vec, row, repl, state) PartitionSpecs for shard_map plumbing.

        Scale slabs ``[n, P/128]`` shard their trailing dim over the same P
        axes — tile boundaries align with shard boundaries because P/k is a
        multiple of 128, so P/128 is a multiple of k.
        """
        vec = PartitionSpec(self.paxes)
        row = PartitionSpec(None, self.paxes)
        repl = PartitionSpec()
        kw = {}
        if self.compressed:
            kw.update(gw_scale=row, infl_scale=row, ef=vec)
        if self.sparse_meta:
            kw.update(gw_touched=row, in_touched=row)
        if self.backend == "indexed":
            kw.update(drops=repl)
        st = EngineState(vec, row, row, repl, repl, **kw)
        return vec, row, repl, st

    def _shmap(self, body, in_specs, out_specs):
        return shard_map(body, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    # --------------------------------------------------------------- init

    def _extra_fields(self, n: int, t: int, make) -> dict:
        """The optional trailing ``EngineState`` fields this engine carries
        (``make(shape, dtype)`` builds each leaf — zeros or SDS)."""
        kw = {}
        if self.sparse_meta:
            kw.update(gw_touched=make((n, t), jnp.int8),
                      in_touched=make((n, t), jnp.int8))
        if self.backend == "indexed":
            kw.update(drops=make((), jnp.int32))
        return kw

    def init(self) -> EngineState:
        n, P = self.n_workers, self.P
        if self.compressed:
            t = self.n_tiles
            state = EngineState(
                g_bar=jnp.zeros((P,), jnp.float32),
                g_workers=jnp.zeros((n, P), jnp.int8),
                inflight=jnp.zeros((n, P), jnp.int8),
                acc_count=jnp.zeros((n,), jnp.int32),
                step=jnp.zeros((), jnp.int32),
                gw_scale=jnp.zeros((n, t), jnp.float32),
                infl_scale=jnp.zeros((n, t), jnp.float32),
                ef=jnp.zeros((P,), jnp.float32),
                **self._extra_fields(n, t, jnp.zeros),
            )
        else:
            state = EngineState(
                g_bar=jnp.zeros((P,), jnp.float32),
                g_workers=jnp.zeros((n, P), self.buffer_dtype),
                inflight=jnp.zeros((n, P), self.buffer_dtype),
                acc_count=jnp.zeros((n,), jnp.int32),
                step=jnp.zeros((), jnp.int32),
                **self._extra_fields(n, self.n_tiles, jnp.zeros),
            )
        if self.mesh is not None:
            state = jax.device_put(state, self.shardings())
        return state

    def state_shapes(self) -> EngineState:
        """Abstract ``EngineState`` (ShapeDtypeStructs) for lowering."""
        n, P = self.n_workers, self.P
        sds = jax.ShapeDtypeStruct
        if self.compressed:
            t = self.n_tiles
            return EngineState(
                g_bar=sds((P,), jnp.float32),
                g_workers=sds((n, P), jnp.int8),
                inflight=sds((n, P), jnp.int8),
                acc_count=sds((n,), jnp.int32),
                step=sds((), jnp.int32),
                gw_scale=sds((n, t), jnp.float32),
                infl_scale=sds((n, t), jnp.float32),
                ef=sds((P,), jnp.float32),
                **self._extra_fields(n, t, sds),
            )
        return EngineState(
            g_bar=sds((P,), jnp.float32),
            g_workers=sds((n, P), self.buffer_dtype),
            inflight=sds((n, P), self.buffer_dtype),
            acc_count=sds((n,), jnp.int32),
            step=sds((), jnp.int32),
            **self._extra_fields(n, self.n_tiles, sds),
        )

    # ------------------------------------------------------------- commit

    def commit(self, state: EngineState, worker: jnp.ndarray,
               grad: jnp.ndarray) -> tuple[EngineState, jnp.ndarray]:
        """Fully-async server iteration (Alg. 1 lines 4-6) on flat ``[P]``.

        O(P) work regardless of backend — there is nothing to fuse or index,
        so all three backends share this implementation.  Elementwise on P,
        so the sharded path is communication-free.

        Compressed formats quantize ``g + ef`` with error feedback and store
        the quantized row itself (payload + per-tile scales), so
        ``g_bar == mean_i dec(g_workers[i])`` holds exactly and
        ``dec + ef' == g + ef`` holds bitwise (core/compression.py).
        Per-shard encoding equals global encoding because scale tiles align
        with P-shard boundaries, so the sharded commit stays collective-free.
        """
        if self.compressed:
            return self._commit_q(state, worker, grad)

        def body(g_bar, g_workers, w, g):
            g = g.astype(jnp.float32)
            old = jax.lax.dynamic_index_in_dim(g_workers, w, axis=0,
                                               keepdims=False)
            g_bar = g_bar + (g - old.astype(jnp.float32)) / self.n_workers
            g_workers = jax.lax.dynamic_update_index_in_dim(
                g_workers, g.astype(g_workers.dtype), w, axis=0)
            return g_bar, g_workers

        if self.mesh is not None:
            vec, row, repl, _ = self._pspecs()
            body = self._shmap(body, in_specs=(vec, row, repl, vec),
                               out_specs=(vec, row))
        g_bar, g_workers = body(state.g_bar, state.g_workers, worker, grad)
        st = state._replace(g_bar=g_bar, g_workers=g_workers,
                            step=state.step + 1)
        return st, g_bar

    def _commit_q(self, state: EngineState, worker: jnp.ndarray,
                  grad: jnp.ndarray) -> tuple[EngineState, jnp.ndarray]:
        codec = self.codec
        sparse = state.gw_touched is not None

        def body(g_bar, gw_q, gw_s, ef, w, g, *targs):
            q, s, dec, ef_new = codec.encode_commit(g.astype(jnp.float32), ef)
            old_q = jax.lax.dynamic_index_in_dim(gw_q, w, axis=0,
                                                 keepdims=False)
            old_s = jax.lax.dynamic_index_in_dim(gw_s, w, axis=0,
                                                 keepdims=False)
            dec_old = codec.decode(old_q, old_s)
            g_bar = g_bar + (dec - dec_old) / self.n_workers
            gw_q = jax.lax.dynamic_update_index_in_dim(gw_q, q, w, axis=0)
            gw_s = jax.lax.dynamic_update_index_in_dim(gw_s, s, w, axis=0)
            out = (g_bar, gw_q, gw_s, ef_new)
            if sparse:
                # keep the invariant "bitmap == touched_tiles(q row)"
                gw_t = jax.lax.dynamic_update_index_in_dim(
                    targs[0], touched_tiles(q), w, axis=0)
                out += (gw_t,)
            return out

        targs = (state.gw_touched,) if sparse else ()
        if self.mesh is not None:
            vec, row, repl, _ = self._pspecs()
            body = self._shmap(
                body,
                in_specs=(vec, row, row, vec, repl, vec)
                + (row,) * len(targs),
                out_specs=(vec, row, row, vec) + (row,) * len(targs))
        out = body(state.g_bar, state.g_workers, state.gw_scale, state.ef,
                   worker, grad, *targs)
        st = state._replace(g_bar=out[0], g_workers=out[1], gw_scale=out[2],
                            ef=out[3], step=state.step + 1)
        if sparse:
            st = st._replace(gw_touched=out[4])
        return st, out[0]

    # -------------------------------------------- sparse commit transport

    def _require_sparse(self, state: EngineState):
        if not self.sparse_meta or state.gw_touched is None:
            raise ValueError(
                "SparseRow transport needs an engine built with "
                "sparse_meta=True (and a state initialized by it)")

    def encode_sparse_commit(self, state: EngineState, worker: jnp.ndarray,
                             grad: jnp.ndarray
                             ) -> tuple[EngineState, SparseRow]:
        """Sender half of the sparse commit: encode one worker's gradient as
        a ``SparseRow`` and advance the error-feedback residual.

        The row's "clear set" is the worker's current touched bitmap — every
        tile the slab holds nonzero for this worker is listed (possibly with
        an all-zero payload), so ``sparse_fold`` can overwrite it and the
        row-replace semantics of ``commit`` are preserved.  Dense O(P) math
        (it reads the full gradient), but the OUTPUT is the O(k * cap) wire
        row; pair with ``sparse_fold`` on the receiver.  ``step`` advances in
        the fold, not here.
        """
        self._require_sparse(state)
        prev = jax.lax.dynamic_index_in_dim(
            state.gw_touched, worker, axis=0, keepdims=False) != 0
        row, ef_new = self.codec.sparse_encode_commit(
            grad.astype(jnp.float32), state.ef, cap=self.cap_tiles,
            include=prev)
        return state._replace(ef=ef_new), row

    def sparse_fold(self, state: EngineState, worker: jnp.ndarray,
                    row: SparseRow) -> tuple[EngineState, jnp.ndarray]:
        """Receiver half: scatter-decode a ``SparseRow`` straight into the
        stored int8 slab row — zero dense ``[P]`` intermediates.

        Work is O(cap * 128): gather the old payload of exactly the listed
        tiles, scatter-add ``(dec_new - dec_old) / n`` into ``g_bar``, and
        scatter payload + scales + bitmap back.  ``g_bar`` matches the dense
        ``commit`` bit-for-bit (untouched tiles would contribute exact +0.0
        there); slab scales of never-listed tiles may go stale vs a dense
        commit, which is decode-invisible (their payload is zero).  Under a
        mesh the row is replicated — it IS the wire format, a few KB — and
        each P-shard folds only its own tiles via a global->local id shift.
        """
        self._require_sparse(state)
        n = self.n_workers
        qtile = self.codec.tile

        def body(g_bar, gw_q, gw_s, gw_t, w, tiles, lanes, vals, scales):
            p_loc = g_bar.shape[0]
            t_loc = p_loc // qtile
            off = jnp.int32(0)
            for a in self.paxes:
                off = off * self.mesh.shape[a] + jax.lax.axis_index(a)
            loc = tiles - off * t_loc
            live = (loc >= 0) & (loc < t_loc)   # pad sentinel (== T) too
            loc = jnp.where(live, loc, t_loc)
            cap, k = lanes.shape
            rows_i = jax.lax.broadcasted_iota(jnp.int32, (cap, k), 0)
            # new tile images [cap, 128]: pad lanes (== 128) are dropped
            img = jnp.zeros((cap, qtile), jnp.int8).at[
                rows_i, lanes.astype(jnp.int32)].set(vals, mode="drop")
            lpos = loc[:, None] * qtile + jax.lax.broadcasted_iota(
                jnp.int32, (cap, qtile), 1)
            lpos = jnp.where(live[:, None], lpos, p_loc)
            old = gw_q.at[w, lpos].get(mode="fill", fill_value=0)
            old_s = gw_s.at[w, loc].get(mode="fill", fill_value=0.0)
            dec_new = img.astype(jnp.float32) * scales[:, None]
            dec_old = old.astype(jnp.float32) * old_s[:, None]
            # gather / elementwise / scatter-SET — NOT a scatter-add: the
            # fold expression must be the exact elementwise graph the dense
            # commit runs (`g_bar + (dec - dec_old) / n`) so XLA gives both
            # the same fused lowering; an add-combining scatter rounds the
            # update separately and can differ in the last bit
            gb_old = g_bar.at[lpos].get(mode="fill", fill_value=0.0)
            g_bar = g_bar.at[lpos].set(gb_old + (dec_new - dec_old) / n,
                                       mode="drop")
            gw_q = gw_q.at[w, lpos].set(img, mode="drop")
            gw_s = gw_s.at[w, loc].set(scales, mode="drop")
            gw_t = gw_t.at[w, loc].set(
                jnp.any(img != 0, axis=-1).astype(jnp.int8), mode="drop")
            return g_bar, gw_q, gw_s, gw_t

        if self.mesh is not None:
            vec, rsp, repl, _ = self._pspecs()
            body = self._shmap(
                body,
                in_specs=(vec, rsp, rsp, rsp, repl, repl, repl, repl, repl),
                out_specs=(vec, rsp, rsp, rsp))
        g_bar, gw_q, gw_s, gw_t = body(
            state.g_bar, state.g_workers, state.gw_scale, state.gw_touched,
            worker, row.tiles, row.lanes, row.vals, row.scales)
        st = state._replace(g_bar=g_bar, g_workers=gw_q, gw_scale=gw_s,
                            gw_touched=gw_t, step=state.step + 1)
        return st, g_bar

    def commit_sparse(self, state: EngineState, worker: jnp.ndarray,
                      grad: jnp.ndarray) -> tuple[EngineState, jnp.ndarray]:
        """Sparse-transport twin of ``commit``: encode then fold.  ``g_bar``
        and the EF residual match the dense commit bit-for-bit whenever the
        touched set fits ``sparse_cap`` (overflow degrades gracefully — the
        dropped tiles' targets re-enter through error feedback)."""
        state, row = self.encode_sparse_commit(state, worker, grad)
        return self.sparse_fold(state, worker, row)

    # -------------------------------------------------------------- round

    def round(self, state: EngineState, fresh: jnp.ndarray,
              start_mask: jnp.ndarray, commit_mask: jnp.ndarray,
              params: Optional[jnp.ndarray] = None,
              eta: Optional[float] = None):
        """Semi-async SPMD round on flat slabs (paper §3 semantics).

        ``fresh`` is the ``[n, P]`` live-model gradient.  Returns
        ``(state, g_bar)``, or ``(state, g_bar, new_params)`` when a flat
        ``params`` vector and ``eta`` are given — the pallas backend folds
        that SGD apply into the same fused pass; the others apply it after.
        """
        if (params is None) != (eta is None):
            raise ValueError("params and eta must be given together")
        sm = start_mask.astype(bool)
        cm = commit_mask.astype(bool)
        self._index_overflow_check(sm, cm)
        g_bar, gw, infl, scales, touched, new_params = self._run_backend(
            state, fresh, sm, cm, params, eta)
        st = state._replace(
            g_bar=g_bar, g_workers=gw, inflight=infl,
            acc_count=jnp.where(sm, 1, state.acc_count + 1).astype(jnp.int32),
            step=state.step + 1,
        )
        if scales is not None:
            st = st._replace(gw_scale=scales[0], infl_scale=scales[1])
        if touched is not None:
            st = st._replace(gw_touched=touched[0], in_touched=touched[1])
        st = self._count_drops(st, sm, cm)
        if params is None:
            return st, g_bar
        return st, g_bar, new_params

    def round_indexed(self, state: EngineState, fresh: jnp.ndarray,
                      start_idx: jnp.ndarray, commit_idx: jnp.ndarray
                      ) -> tuple[EngineState, jnp.ndarray]:
        """Round with host-precomputed padded index arrays (legacy entry
        point of the indexed backend; indices == n are dropped)."""
        if self.accumulate:
            raise ValueError(
                "round_indexed cannot express the accumulate running-mean "
                "latch; use round() with the reference backend")

        if self.sparse_meta:
            def body(st, f, si, ci):
                return self._round_sparse_indexed(st, f, si, ci)
            out_arity = 7
        elif self.compressed:
            def body(st, f, si, ci):
                return self._round_indexed_q(st, f, si, ci)
            out_arity = 5
        else:
            def body(st, f, si, ci):
                return self._round_indexed(st, f, si, ci)
            out_arity = 3

        if self.mesh is not None:
            vec, row, repl, sspec = self._pspecs()
            out_specs = (vec, row, row) + (row,) * (out_arity - 3)
            body = self._shmap(body, in_specs=(sspec, row, repl, repl),
                               out_specs=out_specs)
        out = body(state, fresh, start_idx, commit_idx)
        g_bar, gw, infl = out[:3]
        # acc_count follows the same rule as round(): a worker starting a job
        # this round resets its counter, everyone else accumulates.
        sm = jnp.zeros((self.n_workers,), bool).at[start_idx].set(
            True, mode="drop")
        st = state._replace(
            g_bar=g_bar, g_workers=gw, inflight=infl,
            acc_count=jnp.where(sm, 1, state.acc_count + 1).astype(jnp.int32),
            step=state.step + 1,
        )
        if out_arity >= 5:
            st = st._replace(gw_scale=out[3], infl_scale=out[4])
        if out_arity == 7:
            st = st._replace(gw_touched=out[5], in_touched=out[6])
        return st, g_bar

    # -------------------------------------------------- fused round+apply

    def round_apply(self, state: EngineState, fresh: jnp.ndarray,
                    start_mask: jnp.ndarray, commit_mask: jnp.ndarray,
                    params: jnp.ndarray, opt_state: FlatOptState,
                    opt: FlatOptimizer):
        """DuDe round fused with the flat optimizer apply, under ONE
        shard_map.

        ``params`` is the flat ``[P]`` f32 master-parameter vector and
        ``opt_state`` the flat slot slabs (``optim.transforms``), both
        sharded exactly like ``g_bar``.  The optimizer step is elementwise
        on P (its only scalar input, the replicated step counter, rides
        along), so the whole server iteration — commit, latch, slot update,
        parameter step — moves ZERO bytes between devices.  The pallas
        backend streams the slots through the fused kernel
        (``dude_round_apply_pallas``); the other backends run the round and
        then ``opt.update`` inside the same shard_map body.

        Returns ``(state', g_bar, params', opt_state')``.
        """
        sm = start_mask.astype(bool)
        cm = commit_mask.astype(bool)
        self._index_overflow_check(sm, cm)
        t_new = opt_state.step + 1
        slots = opt_state.slots
        fused = self.backend == "pallas" and opt.name in SLOT_STREAMS
        codec = self.codec

        def body(st, f, a, b, w, t, sl):
            touched = ()
            if fused:
                bc = None
                if opt.name == "adamw":
                    hp = opt.hp
                    t32 = t.astype(jnp.float32)
                    bc = jnp.stack([1 - hp["b1"] ** t32, 1 - hp["b2"] ** t32])
                leaves, sdef = jax.tree_util.tree_flatten(sl)
                if self.sparse_meta:
                    (gw, gw_s, gw_t, infl, infl_s, in_t, g_bar, w_new,
                     new_leaves) = dude_round_apply_sparse_pallas(
                        b, a, self._sparse_blk(st, b),
                        f.astype(jnp.float32), st.g_workers, st.gw_scale,
                        st.gw_touched, st.inflight, st.infl_scale,
                        st.in_touched, st.g_bar, w, tuple(leaves), bc,
                        kind=opt.name, hp=opt.hparams, topk=codec.topk,
                        tile=self.tile, interpret=self._interpret())
                    scales = (gw_s, infl_s)
                    touched = (gw_t, in_t)
                elif self.compressed:
                    (gw, gw_s, infl, infl_s, g_bar, w_new,
                     new_leaves) = dude_round_apply_q_pallas(
                        b, a, f.astype(jnp.float32), st.g_workers,
                        st.gw_scale, st.inflight, st.infl_scale, st.g_bar,
                        w, tuple(leaves), bc, kind=opt.name, hp=opt.hparams,
                        fmt=codec.format, topk=codec.topk, tile=self.tile,
                        interpret=self._interpret())
                    scales = (gw_s, infl_s)
                else:
                    # the kernel widens fresh rows itself: an f32 copy of
                    # a bf16 [n, P] gradient slab here would cost 4nP bytes
                    gw, infl, g_bar, w_new, new_leaves = \
                        dude_round_apply_pallas(
                            b, a, f, st.g_workers,
                            st.inflight, st.g_bar, w, tuple(leaves), bc,
                            kind=opt.name, hp=opt.hparams, tile=self.tile,
                            interpret=self._interpret())
                    scales = ()
                sl_new = jax.tree_util.tree_unflatten(sdef, list(new_leaves))
            else:
                if self.compressed:
                    out = self._round_plain_q(st, f, a, b)
                    g_bar, gw, infl = out[:3]
                    scales = out[3:5]
                    touched = out[5:7]   # () unless sparse_meta
                else:
                    g_bar, gw, infl = self._round_plain(st, f, a, b)
                    scales = ()
                w_new, sl_new = opt.update(w, g_bar, sl, t)
            return (g_bar, gw, infl, w_new, sl_new) + scales + touched

        n_touch = 2 if self.sparse_meta else 0
        if self.mesh is not None:
            vec, row, repl, sspec = self._pspecs()
            slot_specs = jax.tree.map(lambda _: vec, slots)
            scale_specs = (row, row) if self.compressed else ()
            body = self._shmap(
                body,
                in_specs=(sspec, row, repl, repl, vec, repl, slot_specs),
                out_specs=(vec, row, row, vec, slot_specs) + scale_specs
                + (row,) * n_touch)
        out = body(state, fresh, sm, cm, params, t_new, slots)
        g_bar, gw, infl, w_new, sl_new = out[:5]
        st = state._replace(
            g_bar=g_bar, g_workers=gw, inflight=infl,
            acc_count=jnp.where(sm, 1, state.acc_count + 1).astype(jnp.int32),
            step=state.step + 1,
        )
        if self.compressed:
            st = st._replace(gw_scale=out[5], infl_scale=out[6])
        if n_touch:
            st = st._replace(gw_touched=out[7], in_touched=out[8])
        st = self._count_drops(st, sm, cm)
        return st, g_bar, w_new, FlatOptState(t_new, sl_new)

    # ----------------------------------------------------- backend driver

    def _round_plain(self, st, f, a, b):
        """One round on the configured backend (no fused apply), from bool
        masks; returns ``(g_bar, g_workers, inflight)``."""
        if self.backend == "pallas":
            g_bar, gw, infl, _ = self._round_pallas(st, f, a, b, None, None)
            return g_bar, gw, infl
        if self.backend == "indexed":
            n = self.n_workers
            k = self.index_width or n
            return self._round_indexed(st, f, masks_to_indices_jnp(a, n)[:k],
                                       masks_to_indices_jnp(b, n)[:k])
        return self._round_reference(st, f, a, b)

    def _round_plain_q(self, st, f, a, b):
        """Compressed-slab twin of ``_round_plain``; returns
        ``(g_bar, gw_q, infl_q, gw_scale, infl_scale)``, extended with
        ``(gw_touched, in_touched)`` on sparse_meta engines."""
        if self.backend == "pallas":
            if self.sparse_meta:
                return self._round_pallas_sparse(st, f, a, b, None, None)[:7]
            out = self._round_pallas_q(st, f, a, b, None, None)
            return out[:5]
        if self.backend == "indexed":
            n = self.n_workers
            k = self.index_width or n
            si = masks_to_indices_jnp(a, n)[:k]
            ci = masks_to_indices_jnp(b, n)[:k]
            if self.sparse_meta:
                return self._round_sparse_indexed(st, f, si, ci)
            return self._round_indexed_q(st, f, si, ci)
        if self.sparse_meta:
            return self._round_sparse_reference(st, f, a, b)
        return self._round_reference_q(st, f, a, b)

    def _run_backend(self, state, fresh, sm, cm, params, eta):
        """Dispatch one round to the backend, under shard_map when meshed.

        The body is elementwise on P (masks/indices are replicated and the
        worker-axis reduction stays inside each P-shard; scale tiles align
        with shard boundaries), so the sharded round needs no collective at
        all.  Returns ``(g_bar, gw, infl, scales_or_None, touched_or_None,
        params_or_None)`` with ``scales = (gw_scale, infl_scale)`` under
        compressed formats and ``touched = (gw_touched, in_touched)`` on
        sparse_meta engines.
        """
        has_params = params is not None
        compressed = self.compressed
        sparse = self.sparse_meta

        def body(st, f, a, b, *wargs):
            w = wargs[0] if wargs else None
            if self.backend == "pallas":
                if sparse:
                    out = self._round_pallas_sparse(st, f, a, b, w, eta)
                    core, w_new = out[:7], out[7]
                elif compressed:
                    out = self._round_pallas_q(st, f, a, b, w, eta)
                    core, w_new = out[:5], out[5]
                else:
                    g_bar, gw, infl, w_new = self._round_pallas(
                        st, f, a, b, w, eta)
                    core = (g_bar, gw, infl)
            else:
                core = (self._round_plain_q(st, f, a, b) if compressed
                        else self._round_plain(st, f, a, b))
                w_new = None
                if w is not None:
                    w_new = (w.astype(jnp.float32)
                             - jnp.float32(eta) * core[0]).astype(w.dtype)
            return tuple(core) + ((w_new,) if wargs else ())

        wargs = (params,) if has_params else ()
        n_scales = 2 if compressed else 0
        n_touch = 2 if sparse else 0
        if self.mesh is not None:
            vec, row, repl, sspec = self._pspecs()
            body = self._shmap(
                body,
                in_specs=(sspec, row, repl, repl) + (vec,) * len(wargs),
                out_specs=(vec, row, row) + (row,) * (n_scales + n_touch)
                + (vec,) * len(wargs))
        out = body(state, fresh, sm, cm, *wargs)
        scales = (out[3], out[4]) if compressed else None
        touched = (out[5], out[6]) if sparse else None
        w_new = out[3 + n_scales + n_touch] if has_params else None
        return out[0], out[1], out[2], scales, touched, w_new

    def _index_overflow_check(self, sm, cm):
        """Satellite of the indexed backend: |C_t| > index_width silently
        drops real commits — surface it per ``index_check``."""
        if self.backend != "indexed" or self.index_check == "off":
            return
        width = self.index_width or self.n_workers
        if width >= self.n_workers:
            return  # full width can never drop
        n_active = jnp.maximum(jnp.sum(sm.astype(jnp.int32)),
                               jnp.sum(cm.astype(jnp.int32)))
        if self.index_check == "checkify":
            checkify.check(
                n_active <= width,
                "DuDeEngine(indexed): {na} active workers exceed "
                "index_width={w}; excess commits/latches are dropped",
                na=n_active, w=jnp.int32(width))
            return

        def warn(na):
            jax.debug.print(
                "WARNING: DuDeEngine(indexed): {na} active workers exceed "
                f"index_width={width}; excess commits/latches are DROPPED",
                na=na)

        jax.lax.cond(n_active > width, warn, lambda na: None, n_active)

    def _count_drops(self, st: EngineState, sm, cm) -> EngineState:
        """Indexed backend: accumulate how many active workers exceeded
        ``index_width`` this round (their latches/commits were dropped) into
        the structured ``drops`` counter — the queryable twin of
        ``_index_overflow_check``'s debug print, surfaced by the train step
        as the ``engine_drops`` metric."""
        if st.drops is None:
            return st
        width = self.index_width or self.n_workers
        over = (jnp.maximum(jnp.sum(sm.astype(jnp.int32)) - width, 0)
                + jnp.maximum(jnp.sum(cm.astype(jnp.int32)) - width, 0))
        return st._replace(drops=st.drops + over)

    # ----------------------------------------------------------- backends

    def _round_reference(self, state, fresh, sm, cm):
        """Masked full sweep over all n rows (paper-faithful oracle)."""
        g32 = fresh.astype(jnp.float32)
        infl32 = state.inflight.astype(jnp.float32)
        gw32 = state.g_workers.astype(jnp.float32)
        delta = cm.astype(jnp.float32)[:, None] * (infl32 - gw32)
        g_bar = state.g_bar + jnp.sum(delta, axis=0) / self.n_workers
        bdt = state.g_workers.dtype
        gw = jnp.where(cm[:, None], infl32.astype(bdt), state.g_workers)
        if self.accumulate:
            # running mean over the job's rounds (beyond-paper variant)
            cnt = state.acc_count.astype(jnp.float32)
            w_new = (1.0 / jnp.where(sm, 1.0, cnt + 1.0))[:, None]
            infl = (infl32 * (1.0 - w_new) + g32 * w_new).astype(bdt)
        else:
            infl = jnp.where(sm[:, None], g32.astype(bdt), state.inflight)
        return g_bar, gw, infl

    def _round_indexed(self, state, fresh, start_idx, commit_idx):
        """Gather/scatter on the k selected rows only (~4kP HBM bytes)."""
        n = self.n_workers
        bdt = state.g_workers.dtype
        rows_in = jnp.take(state.inflight, commit_idx, axis=0, mode="fill",
                           fill_value=0).astype(jnp.float32)
        rows_gw = jnp.take(state.g_workers, commit_idx, axis=0, mode="fill",
                           fill_value=0).astype(jnp.float32)
        valid = (commit_idx < n).astype(jnp.float32)[:, None]
        g_bar = state.g_bar + jnp.sum((rows_in - rows_gw) * valid, axis=0) / n
        gw = state.g_workers.at[commit_idx].set(rows_in.astype(bdt),
                                                mode="drop")
        fresh_rows = jnp.take(fresh.astype(jnp.float32), start_idx, axis=0,
                              mode="fill", fill_value=0)
        infl = state.inflight.at[start_idx].set(fresh_rows.astype(bdt),
                                                mode="drop")
        return g_bar, gw, infl

    def _round_pallas(self, state, fresh, sm, cm, params, eta):
        """Fused single-pass kernel; optional in-pass SGD apply.  Under
        shard_map the kernel sees the local ``[n, P/k]`` slabs and tiles
        them with ``self.tile``."""
        w = params if params is not None else jnp.zeros_like(state.g_bar)
        gw, infl, g_bar, w_new = dude_update_pallas(
            cm, sm, fresh, state.g_workers, state.inflight, state.g_bar, w,
            eta=float(eta) if eta is not None else 0.0,
            tile=self.tile, interpret=self._interpret(),
        )
        return g_bar, gw, infl, (w_new if params is not None else None)

    # ------------------------------------------------ compressed backends

    def _round_reference_q(self, state, fresh, sm, cm):
        """Masked full sweep over quantized slabs: dequantize both slabs,
        fold the delta in f32, copy committed rows quantized (payload +
        scales, no re-quantization), latch fresh rows through the codec."""
        codec = self.codec
        infl32 = codec.decode(state.inflight, state.infl_scale)
        gw32 = codec.decode(state.g_workers, state.gw_scale)
        delta = cm.astype(jnp.float32)[:, None] * (infl32 - gw32)
        g_bar = state.g_bar + jnp.sum(delta, axis=0) / self.n_workers
        gw_q = jnp.where(cm[:, None], state.inflight, state.g_workers)
        gw_s = jnp.where(cm[:, None], state.infl_scale, state.gw_scale)
        q_f, s_f = codec.encode(fresh.astype(jnp.float32))
        infl_q = jnp.where(sm[:, None], q_f, state.inflight)
        infl_s = jnp.where(sm[:, None], s_f, state.infl_scale)
        return g_bar, gw_q, infl_q, gw_s, infl_s

    def _round_indexed_q(self, state, fresh, start_idx, commit_idx):
        """Gather/scatter twin on the k selected quantized rows only."""
        n = self.n_workers
        codec = self.codec
        rows_in_q = jnp.take(state.inflight, commit_idx, axis=0,
                             mode="fill", fill_value=0)
        rows_in_s = jnp.take(state.infl_scale, commit_idx, axis=0,
                             mode="fill", fill_value=0)
        rows_gw_q = jnp.take(state.g_workers, commit_idx, axis=0,
                             mode="fill", fill_value=0)
        rows_gw_s = jnp.take(state.gw_scale, commit_idx, axis=0,
                             mode="fill", fill_value=0)
        rows_in = codec.decode(rows_in_q, rows_in_s)
        rows_gw = codec.decode(rows_gw_q, rows_gw_s)
        valid = (commit_idx < n).astype(jnp.float32)[:, None]
        g_bar = state.g_bar + jnp.sum((rows_in - rows_gw) * valid, axis=0) / n
        gw_q = state.g_workers.at[commit_idx].set(rows_in_q, mode="drop")
        gw_s = state.gw_scale.at[commit_idx].set(rows_in_s, mode="drop")
        fresh_rows = jnp.take(fresh.astype(jnp.float32), start_idx, axis=0,
                              mode="fill", fill_value=0)
        q_f, s_f = codec.encode(fresh_rows)
        infl_q = state.inflight.at[start_idx].set(q_f, mode="drop")
        infl_s = state.infl_scale.at[start_idx].set(s_f, mode="drop")
        return g_bar, gw_q, infl_q, gw_s, infl_s

    def _round_pallas_q(self, state, fresh, sm, cm, params, eta):
        """Fused quantized kernel; optional in-pass SGD apply.  Returns
        ``(g_bar, gw_q, infl_q, gw_scale, infl_scale, params')``."""
        codec = self.codec
        w = params if params is not None else jnp.zeros_like(state.g_bar)
        gw_q, gw_s, infl_q, infl_s, g_bar, w_new, _ = \
            dude_round_apply_q_pallas(
                cm, sm, fresh.astype(jnp.float32), state.g_workers,
                state.gw_scale, state.inflight, state.infl_scale,
                state.g_bar, w, kind="sgd",
                hp=(("lr", float(eta) if eta is not None else 0.0),),
                fmt=codec.format, topk=codec.topk, tile=self.tile,
                interpret=self._interpret(),
            )
        return g_bar, gw_q, infl_q, gw_s, infl_s, \
            (w_new if params is not None else None)

    # --------------------------------------------------- sparse backends

    def _sparse_blk(self, st: EngineState, cm) -> jnp.ndarray:
        """Per-Pallas-block activity flags ``[P/tile] i32``: does any
        committing row touch any scale tile of the block in either slab?
        Computed OUTSIDE the kernel from the ``[n, P/128]`` bitmaps, so the
        gate costs O(n * P/128) metadata reads, never payload."""
        act = cm[:, None] & ((st.gw_touched | st.in_touched) != 0)
        any_t = jnp.any(act, axis=0)                     # [t_local]
        per = self.tile // self.codec.tile
        any_t = jnp.pad(any_t, (0, -any_t.shape[0] % per))  # ragged block
        return jnp.any(any_t.reshape(-1, per), axis=-1).astype(jnp.int32)

    def _round_sparse_reference(self, state, fresh, sm, cm):
        """Tile-gated masked sweep — the plain-jnp oracle of the sparse
        round.  The fold touches only tiles live in either bitmap of a
        committing row; this is bit-for-bit the dense ``topk_ef`` sweep
        because untouched tiles hold zero payload and decode to exact +0.0
        (and ``g_bar`` is never -0.0).  Scale slabs copy densely — they are
        1/128 of the payload and keeping them bitwise-identical to the dense
        path removes the stale-scale caveat from the round entirely.
        Returns the 5-tuple plus ``(gw_touched, in_touched)``."""
        codec = self.codec
        n = self.n_workers
        qtile = codec.tile
        infl32 = codec.decode(state.inflight, state.infl_scale)
        gw32 = codec.decode(state.g_workers, state.gw_scale)
        act = cm[:, None] & ((state.gw_touched | state.in_touched) != 0)
        gate = jnp.broadcast_to(
            act[:, :, None], act.shape + (qtile,)).reshape(infl32.shape)
        delta = jnp.where(gate, infl32 - gw32, 0.0)
        g_bar = state.g_bar + jnp.sum(delta, axis=0) / n
        gw_q = jnp.where(cm[:, None], state.inflight, state.g_workers)
        gw_s = jnp.where(cm[:, None], state.infl_scale, state.gw_scale)
        gw_t = jnp.where(cm[:, None], state.in_touched, state.gw_touched)
        q_f, s_f = codec.encode(fresh.astype(jnp.float32))
        infl_q = jnp.where(sm[:, None], q_f, state.inflight)
        infl_s = jnp.where(sm[:, None], s_f, state.infl_scale)
        in_t = jnp.where(sm[:, None], touched_tiles(q_f),
                         state.in_touched)
        return g_bar, gw_q, infl_q, gw_s, infl_s, gw_t, in_t

    def _round_sparse_indexed(self, state, fresh, start_idx, commit_idx):
        """Gather/scatter sparse twin: gathers the k selected rows AND their
        bitmaps, gating the fold per gathered tile.  Bitwise equal to
        ``_round_indexed_q`` (same +0.0 argument as the reference twin)."""
        n = self.n_workers
        codec = self.codec
        qtile = codec.tile
        take = lambda a, i: jnp.take(a, i, axis=0, mode="fill", fill_value=0)
        rows_in_q = take(state.inflight, commit_idx)
        rows_in_s = take(state.infl_scale, commit_idx)
        rows_gw_q = take(state.g_workers, commit_idx)
        rows_gw_s = take(state.gw_scale, commit_idx)
        rows_in_t = take(state.in_touched, commit_idx)
        rows_gw_t = take(state.gw_touched, commit_idx)
        act = (rows_in_t | rows_gw_t) != 0
        gate = jnp.broadcast_to(
            act[:, :, None], act.shape + (qtile,)).reshape(rows_in_q.shape)
        diff = jnp.where(gate,
                         codec.decode(rows_in_q, rows_in_s)
                         - codec.decode(rows_gw_q, rows_gw_s), 0.0)
        valid = (commit_idx < n).astype(jnp.float32)[:, None]
        g_bar = state.g_bar + jnp.sum(diff * valid, axis=0) / n
        gw_q = state.g_workers.at[commit_idx].set(rows_in_q, mode="drop")
        gw_s = state.gw_scale.at[commit_idx].set(rows_in_s, mode="drop")
        gw_t = state.gw_touched.at[commit_idx].set(rows_in_t, mode="drop")
        fresh_rows = jnp.take(fresh.astype(jnp.float32), start_idx, axis=0,
                              mode="fill", fill_value=0)
        q_f, s_f = codec.encode(fresh_rows)
        infl_q = state.inflight.at[start_idx].set(q_f, mode="drop")
        infl_s = state.infl_scale.at[start_idx].set(s_f, mode="drop")
        in_t = state.in_touched.at[start_idx].set(
            touched_tiles(q_f), mode="drop")
        return g_bar, gw_q, infl_q, gw_s, infl_s, gw_t, in_t

    def _round_pallas_sparse(self, state, fresh, sm, cm, params, eta):
        """Touched-tile-gated fused kernel: the precomputed block activity
        array lets the kernel skip the dequant+fold of every block no
        committing row touches; the fresh latch, scale copies, bitmaps, and
        optimizer tail stay dense, so the result is bit-for-bit the dense
        ``topk_ef`` round.  Returns ``(g_bar, gw_q, infl_q, gw_scale,
        infl_scale, gw_touched, in_touched, params')``."""
        codec = self.codec
        w = params if params is not None else jnp.zeros_like(state.g_bar)
        (gw_q, gw_s, gw_t, infl_q, infl_s, in_t, g_bar, w_new, _) = \
            dude_round_apply_sparse_pallas(
                cm, sm, self._sparse_blk(state, cm),
                fresh.astype(jnp.float32), state.g_workers, state.gw_scale,
                state.gw_touched, state.inflight, state.infl_scale,
                state.in_touched, state.g_bar, w, kind="sgd",
                hp=(("lr", float(eta) if eta is not None else 0.0),),
                topk=codec.topk, tile=self.tile,
                interpret=self._interpret(),
            )
        return (g_bar, gw_q, infl_q, gw_s, infl_s, gw_t, in_t,
                w_new if params is not None else None)
