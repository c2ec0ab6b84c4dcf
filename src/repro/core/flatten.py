"""Pytree <-> flat-buffer ravel layer for the ServerEngine.

The DuDe server iteration is elementwise over Theta(n * p) buffer state, so
the engine stores all of it as padded flat slabs: ``g_bar`` as ``[P]`` and the
per-worker buffers as ``[n, P]``, where ``P`` is the total parameter count
rounded up to a lane multiple (so the fused Pallas kernel always sees
tileable shapes).  This module owns the mapping between gradient pytrees and
those slabs.

A ``FlatSpec`` is built once per (treedef, leaf shapes/dtypes) and cached: it
records the treedef plus a segment table (offset/size/shape/dtype per leaf)
so ravel is a cast+reshape+concat and unravel is a slice+reshape+cast — both
fuse into neighbouring ops under jit.  Padding is zero-filled and ignored on
unravel; zeros are a fixed point of every engine update, so the pad lanes
never contaminate real state.

Shard-aligned layout: ``make_flat_spec(tree, mesh_axis_size=k)`` pads ``P``
up to a multiple of ``k * PAD_MULTIPLE`` so the flat vector splits into ``k``
contiguous, equally sized, lane-aligned shards — one per device on a P-axis
mesh.  The split is purely positional (segment ranges, not leaf boundaries):
a shard may own the tail of one leaf and the head of the next, and all pad
lanes land in the trailing shard, so no shard ever needs remote elements.
``shard_ranges`` / ``shard_segments`` expose the resulting per-shard segment
table for sharding rules, checkpoint layouts, and debugging.

TP-native exchange: ``unravel_sharded`` / ``ravel_stacked_sharded`` are the
mesh-native twins of ``unravel`` / ``ravel_stacked`` — they move leaves
between the segment-range P-shards and the params' Megatron-TP layout
WITHOUT ever materializing the full ``[P]`` vector (or ``[n, P]`` slab) on
any device.  The k windows of the flat vector circulate around a ppermute
ring; each device copies exactly its TP-block elements out of (into) each
passing window, positions precomputed in a static ``FlatTpPlan``
(``sharding.specs.flat_to_tp_plan``).  Bit-for-bit equal to the replicated
path: elements are copied, never re-reduced.

Documented in docs/engine.md — "Flat layout", "Segment table (FlatSpec)",
"Sharding the flat layout" and "TP-native unravel".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec

Pytree = Any

__all__ = ["FlatSpec", "make_flat_spec", "PAD_MULTIPLE"]

# Lane width of the TPU vector unit: padding P to a multiple of this keeps
# every backend (and the Pallas tile chooser) shape-happy.
PAD_MULTIPLE = 128


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Segment table mapping one pytree layout to a padded flat vector."""

    treedef: Any
    shapes: tuple          # per-leaf shapes
    dtypes: tuple          # per-leaf dtypes (restored on unravel)
    sizes: tuple           # per-leaf element counts
    offsets: tuple         # per-leaf start offset into the flat vector
    size: int              # sum(sizes), before padding
    padded_size: int       # P: size rounded up to mesh_axis_size*PAD_MULTIPLE
    mesh_axis_size: int = 1  # k: number of contiguous P-axis shards

    def __post_init__(self):
        # shard_segments memo: the per-shard table is pure spec geometry but
        # costs a Python loop over all leaves; the TP-native exchange plan
        # reads it per shard per build, so cache per spec instance.  Not a
        # dataclass field: eq/hash stay value-based.
        object.__setattr__(self, "_segments_cache", {})

    # ----------------------------------------------------------- sharding

    @property
    def shard_size(self) -> int:
        """Elements per P-axis shard (``P / k``; a PAD_MULTIPLE multiple)."""
        return self.padded_size // self.mesh_axis_size

    def shard_ranges(self) -> tuple:
        """Per-shard ``(start, stop)`` offsets into the flat vector.  Shard
        ``s`` owns the contiguous slice ``[s*P/k, (s+1)*P/k)``; all pad lanes
        (offsets >= ``size``) fall in the trailing shard(s)."""
        w = self.shard_size
        return tuple((s * w, (s + 1) * w) for s in range(self.mesh_axis_size))

    def shard_segments(self, shard: int) -> tuple:
        """Segment table of one shard: ``(leaf_index, leaf_start, leaf_stop)``
        triples giving, in leaf-local element coordinates, the slice of each
        leaf that shard ``shard`` owns.  Pad lanes are not listed.  Memoized
        per spec (the table is static geometry)."""
        hit = self._segments_cache.get(shard)
        if hit is not None:
            return hit
        lo, hi = self.shard_ranges()[shard]
        out = []
        for i, (off, sz) in enumerate(zip(self.offsets, self.sizes)):
            a, b = max(lo, off), min(hi, off + sz)
            if a < b:
                out.append((i, a - off, b - off))
        result = tuple(out)
        self._segments_cache[shard] = result
        return result

    # ------------------------------------------------------------- ravel

    def ravel(self, tree: Pytree, dtype=jnp.float32) -> jnp.ndarray:
        """Pytree with leaves of ``self.shapes`` -> flat ``[P]`` in ``dtype``."""
        leaves = self.treedef.flatten_up_to(tree)
        flat = [jnp.asarray(x).astype(dtype).reshape(-1) for x in leaves]
        return self._pad(jnp.concatenate(flat) if flat else jnp.zeros((0,), dtype))

    def ravel_stacked(self, tree: Pytree, dtype=jnp.float32) -> jnp.ndarray:
        """Pytree with ``[n, *shape]`` leaves -> ``[n, P]`` in ``dtype``."""
        leaves = self.treedef.flatten_up_to(tree)
        n = jnp.shape(leaves[0])[0]
        flat = [jnp.asarray(x).astype(dtype).reshape(n, -1) for x in leaves]
        return self._pad(jnp.concatenate(flat, axis=-1), n)

    def _pad(self, flat: jnp.ndarray, n: int | None = None) -> jnp.ndarray:
        pad = self.padded_size - self.size
        if pad == 0:
            return flat
        widths = ((0, 0), (0, pad)) if n is not None else ((0, pad),)
        return jnp.pad(flat, widths)

    # ----------------------------------------------------------- unravel

    def unravel(self, flat: jnp.ndarray, cast: bool = True) -> Pytree:
        """Flat ``[P]`` -> pytree with the spec's shapes (and dtypes if
        ``cast``; otherwise leaves keep ``flat.dtype``)."""
        leaves = []
        for off, sz, shp, dt in zip(self.offsets, self.sizes, self.shapes,
                                    self.dtypes):
            x = flat[off:off + sz].reshape(shp)
            leaves.append(x.astype(dt) if cast else x)
        return jax.tree.unflatten(self.treedef, leaves)

    def unravel_stacked(self, flat: jnp.ndarray, cast: bool = True) -> Pytree:
        """``[n, P]`` -> pytree with ``[n, *shape]`` leaves."""
        n = flat.shape[0]
        leaves = []
        for off, sz, shp, dt in zip(self.offsets, self.sizes, self.shapes,
                                    self.dtypes):
            x = flat[:, off:off + sz].reshape((n,) + shp)
            leaves.append(x.astype(dt) if cast else x)
        return jax.tree.unflatten(self.treedef, leaves)

    # ------------------------------------------------- TP-native exchange

    def tp_plan(self, mesh, param_sh: Pytree, axes: Any = None):
        """The static P-shards <-> TP-blocks exchange plan for this spec
        (``sharding.specs.flat_to_tp_plan``; cached)."""
        from ..sharding.specs import flat_to_tp_plan
        return flat_to_tp_plan(self, mesh, param_sh, axes=axes)

    def unravel_sharded(self, flat: jnp.ndarray, mesh, param_sh: Pytree = None,
                        *, axes: Any = None, plan=None,
                        cast: bool = True) -> Pytree:
        """Mesh-native ``unravel``: segment-range P-shards of ``flat`` ->
        leaves in their Megatron-TP layout, with NO device ever holding the
        full ``[P]`` vector.

        The k windows of the flat vector circulate around a ppermute ring
        (k-1 hops of ``[P/k]`` each); at every hop each device copies the
        block elements the passing window carries for it, at positions
        precomputed in the plan.  Values are copied, never combined, so the
        result is bit-for-bit ``unravel`` of the gathered vector.  Peak live
        bytes per device: ``plan.peak_bytes`` — O(P/k + sum of TP blocks)
        instead of the replicated path's O(P)."""
        if plan is None:
            plan = self.tp_plan(mesh, param_sh, axes=axes)
        if plan.k <= 1:
            return self.unravel(flat, cast=cast)
        Wh = plan.window >> _LO_BITS  # window rows of _LO lanes each
        sizes = dict(zip(plan.axes, plan.mesh_shape))

        def body(local):  # [W]: this device's window of the flat vector
            s = _lin_index(plan.axes, sizes)
            digs = [_leaf_digits(lf, sizes) for lf in plan.leaves]

            def take(accs, buf, w):
                # copy my block elements carried by window ``w``
                whi = w * Wh
                buf2 = buf.reshape(Wh, _LO)
                out = []
                for lf, (hi, lo), acc in zip(plan.leaves, digs, accs):
                    parts = []
                    for a, b in _chunks(lf.block_size):
                        row = hi[a:b] - whi
                        ok = (row >= 0) & (row < Wh)
                        vals = buf2[jnp.clip(row, 0, Wh - 1), lo[a:b]]
                        parts.append(jnp.where(ok, vals, acc[a:b]))
                    out.append(parts[0] if len(parts) == 1
                               else jnp.concatenate(parts))
                return tuple(out)

            accs = tuple(jnp.zeros((lf.block_size,), local.dtype)
                         for lf in plan.leaves)
            accs = take(accs, local, s)
            perm = [(i, (i - 1) % plan.k) for i in range(plan.k)]

            def hop(r, carry):
                buf, accs = carry
                buf = jax.lax.ppermute(buf, plan.axes, perm)
                return buf, take(accs, buf, (s + r) % plan.k)

            _, accs = jax.lax.fori_loop(1, plan.k, hop, (local, accs))
            outs = []
            for lf, acc in zip(plan.leaves, accs):
                x = acc.reshape(lf.block_shape)
                outs.append(x.astype(lf.dtype) if cast else x)
            return tuple(outs)

        fn = shard_map(
            body, mesh=mesh, in_specs=PartitionSpec(plan.axes),
            out_specs=tuple(PartitionSpec(*lf.entries) for lf in plan.leaves),
            check_vma=False)
        return jax.tree.unflatten(self.treedef, list(fn(flat)))

    def ravel_stacked_sharded(self, tree: Pytree, mesh,
                              param_sh: Pytree = None, dtype=jnp.float32,
                              *, axes: Any = None, plan=None) -> jnp.ndarray:
        """Mesh-native ``ravel_stacked``: ``[n, *shape]`` leaves in their TP
        layout -> the ``[n, P]`` slab in segment-range P-shards, with no
        replicated ``[n, P]`` (or full-leaf) intermediate.

        The reverse ring: each device's ``[n, P/k]`` window accumulator
        makes one lap, visiting every device; each device writes its block
        values into the positions the passing accumulator owns.  The flat
        positions of distinct (device, leaf) contributions are disjoint
        (replicated leaves contribute from their first replica only), so
        the writes are pure scatters — bit-for-bit ``ravel_stacked``,
        including signed zeros.  Pad lanes stay zero."""
        if plan is None:
            plan = self.tp_plan(mesh, param_sh, axes=axes)
        leaves = self.treedef.flatten_up_to(tree)
        if plan.k <= 1:
            return self.ravel_stacked(tree, dtype)
        n = int(jnp.shape(leaves[0])[0])
        W = plan.window
        Wh = W >> _LO_BITS
        sizes = dict(zip(plan.axes, plan.mesh_shape))

        def body(*blocks):  # per leaf: [n, *block_shape]
            s = _lin_index(plan.axes, sizes)
            digs = [_leaf_digits(lf, sizes) for lf in plan.leaves]
            masks = [_replica_mask(lf, plan.axes) for lf in plan.leaves]

            def contrib(acc, h):
                # write my block values owned by window ``h``
                whi = h * Wh
                acc3 = acc.reshape(n, Wh, _LO)
                for lf, (hi, lo), mk, blk in zip(plan.leaves, digs, masks,
                                                 blocks):
                    vals = blk.reshape((n, -1)).astype(dtype)
                    for a, b in _chunks(lf.block_size):
                        row = hi[a:b] - whi
                        row = jnp.where(mk & (row >= 0) & (row < Wh),
                                        row, Wh)
                        acc3 = acc3.at[:, row, lo[a:b]].set(vals[:, a:b],
                                                            mode="drop")
                return acc3.reshape(n, W)

            acc = contrib(jnp.zeros((n, W), dtype), (s - 1) % plan.k)
            perm = [(i, (i + 1) % plan.k) for i in range(plan.k)]

            def hop(r, acc):
                acc = jax.lax.ppermute(acc, plan.axes, perm)
                return contrib(acc, (s - r - 1) % plan.k)

            acc = jax.lax.fori_loop(1, plan.k, hop, acc)
            return acc

        fn = shard_map(
            body, mesh=mesh,
            in_specs=tuple(PartitionSpec(None, *lf.entries)
                           for lf in plan.leaves),
            out_specs=PartitionSpec(None, plan.axes), check_vma=False)
        return fn(*leaves)


# Window addressing is two int32 digits, ``pos == hi * _LO + lo``: a jit
# traced with x64 off canonicalizes every jaxpr literal/constant to int32 at
# LOWERING time regardless of the equation's aval, so int64 position vectors
# (and even small literals sitting next to an i64 tracer, or the axis-size
# constants jnp's own index normalization inserts) cannot cross the lowering
# of a >2^31-element spec.  With 128 lanes per row every digit stays below
# 2^31 for any P < 2^38 (~274 B params); ``flat_to_tp_plan`` rejects larger.
_LO_BITS = 7
_LO = 1 << _LO_BITS

# XLA caps a single gather/scatter at 2^31 indices; leaves past _CHUNK block
# elements (the 110B embedding on a small host mesh) exchange in static
# slices.  One chunk — the overwhelmingly common case — lowers identically
# to the unchunked op.
_CHUNK = 1 << 30


def _chunks(size: int):
    return [(a, min(a + _CHUNK, size)) for a in range(0, size, _CHUNK)]


def _lin_index(axes: tuple, sizes: dict) -> jnp.ndarray:
    """This device's linear P-shard index over ``axes`` (major -> minor),
    matching the shard order of ``PartitionSpec((axes,))``."""
    idx = jnp.asarray(0, jnp.int32)
    for a in axes:
        idx = idx * sizes[a] + jax.lax.axis_index(a)
    return idx


def _leaf_digits(lf, sizes: dict):
    """Digits ``(pos >> 7, pos & 127)`` of the global flat positions of this
    device's TP block of leaf ``lf`` (``pos = offset + sum_d (block_start_d +
    coord_d) * stride_d``, row-major ``[block_size]``), int32 throughout and
    fully traced — no materialized position constants, so the lowered module
    stays O(sum of block dims), not O(block elements).

    Every term's digits are formed from int32 pieces: splitting a stride
    ``m = (m >> 7)·128 + (m & 127)``, the high digit ``c·(m >> 7) +
    (c·(m & 127) >> 7)`` of a term is bounded by ``pos / 128 < 2^31``
    (``flat_to_tp_plan`` rejects ``P >= 2^38``), and the low digits sum to
    under ``rank·2^31`` before the final carry."""
    rank = len(lf.shape)

    def digits(c, m):  # digits of c*m: c int32 scalar/vector, m static < P
        t = c * np.int32(m & (_LO - 1))  # < dim * 128
        return c * np.int32(m >> _LO_BITS) + (t >> _LO_BITS), t & (_LO - 1)

    hi = jnp.asarray(lf.offset >> _LO_BITS, jnp.int32)
    lo = jnp.asarray(lf.offset & (_LO - 1), jnp.int32)
    for d in range(rank):
        bs = lf.block_shape[d]
        if bs * (lf.strides[d] & (_LO - 1)) > np.iinfo(np.int32).max:
            raise NotImplementedError(
                f"leaf dim {d} of shape {lf.shape}: dim * (stride % 128) "
                f"overflows int32 in the digit addressing")
        coords = jnp.arange(bs, dtype=jnp.int32)
        if lf.entries[d] is not None:
            bidx = jnp.asarray(0, jnp.int32)
            for a in lf.entries[d]:
                bidx = bidx * sizes[a] + jax.lax.axis_index(a)
            bhi, blo = digits(bidx, bs * lf.strides[d])  # block start
        else:
            bhi = blo = jnp.asarray(0, jnp.int32)
        chi, clo = digits(coords, lf.strides[d])
        shape = [1] * rank
        shape[d] = bs
        hi = hi + (bhi + chi).reshape(shape)
        lo = lo + (blo + clo).reshape(shape)
    hi = jnp.broadcast_to(hi + (lo >> _LO_BITS), lf.block_shape).reshape(-1)
    lo = jnp.broadcast_to(lo & (_LO - 1), lf.block_shape).reshape(-1)
    return hi, lo


def _replica_mask(lf, axes: tuple) -> jnp.ndarray:
    """True on the first replica of this leaf's TP block: a leaf replicated
    over some P-axis group axes exists on several devices, but only one may
    contribute it to the slab."""
    used = set(lf.tp_axes)
    m = None
    for a in axes:
        if a not in used:
            c = jax.lax.axis_index(a) == 0
            m = c if m is None else (m & c)
    return jnp.asarray(True) if m is None else m


_SPEC_CACHE: dict = {}


def make_flat_spec(tree: Pytree, pad_multiple: int = PAD_MULTIPLE,
                   mesh_axis_size: int = 1) -> FlatSpec:
    """Build (or fetch from cache) the FlatSpec for ``tree``'s layout.

    ``tree`` may hold arrays or ShapeDtypeStructs; only structure, shapes and
    dtypes matter.  Safe to call at trace time — everything here is static.

    ``mesh_axis_size=k`` makes the layout shard-aligned: ``P`` is padded to a
    multiple of ``k * pad_multiple`` so the vector splits into ``k`` equal
    contiguous lane-aligned shards (see ``FlatSpec.shard_ranges``).
    """
    if mesh_axis_size < 1:
        raise ValueError(f"mesh_axis_size={mesh_axis_size} must be >= 1")
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(jnp.shape(x)) for x in leaves)
    dtypes = tuple(jnp.result_type(x) for x in leaves)
    key = (treedef, shapes, tuple(np.dtype(d).name for d in dtypes),
           pad_multiple, mesh_axis_size)
    spec = _SPEC_CACHE.get(key)
    if spec is not None:
        return spec
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    size = int(sum(sizes))
    chunk = pad_multiple * mesh_axis_size
    padded = max(chunk, -(-size // chunk) * chunk)
    spec = FlatSpec(treedef, shapes, dtypes, sizes, offsets, size, padded,
                    mesh_axis_size)
    _SPEC_CACHE[key] = spec
    return spec
