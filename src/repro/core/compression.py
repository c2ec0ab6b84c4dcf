"""Flat-slab commit codec: tiled int8 + error feedback over ``[P]`` vectors.

DuDe-ASGD's server memory is Theta(n * P): one stored gradient per worker plus
one in-flight gradient per worker.  At 100B+ parameter scale the ``[n, P]``
slab dominates HBM, and every per-arrival commit moves a full-precision row.
This module provides the storage/wire format that cuts both ~4x while keeping
the dual-delay protocol exactly intact:

* ``quantize`` / ``dequantize`` — symmetric int8 with a **per-128-lane-tile**
  f32 scale: the smallest POWER OF TWO >= ``max|x_t| / 127``.  One scale per
  tile, never per tensor: a single scale across a full ``[P]`` slab would
  collapse the precision of small segments.  128 lanes is the engine's pad
  granularity (``flatten.PAD_MULTIPLE``), so tile boundaries always align
  with P-axis shard boundaries and per-shard encoding equals global
  encoding.  Power-of-two scales cost at most one extra bit of error
  (error <= scale/2 <= max|x_t|/127) and make ``q * scale`` / ``x / scale``
  EXACT in f32 — the decode value cannot shift under compiler fusion (XLA
  contracts ``q*scale`` into neighboring subtractions as an FMA; with an
  exact product the contraction is value-identical).
* ``topk_mask`` — per-tile magnitude top-k sparsifier, applied *before*
  quantization so the top-k format shares all int8 storage and kernel
  machinery (dropped values re-enter through error feedback).  Selection is
  DETERMINISTIC: exactly ``k`` lanes survive per tile, magnitude ties broken
  toward the lower lane index — the same op sequence lowers identically
  under XLA and inside the Pallas kernel, so every backend picks the same
  survivors bit-for-bit.
* ``SparseRow`` — the index-carrying wire format of one ``topk_ef`` row:
  per-touched-tile survivor lane indices (uint8) + int8 values + f32
  power-of-two scales + an i32 touched-tile index list with a live count.
  A commit or snapshot delta then costs O(k * tiles_touched) bytes on the
  wire and in slab writes instead of O(P) — ``sparse_encode`` /
  ``sparse_decode`` round-trip bit-exactly against the dense ``(q, scale)``
  pair, and ``CommitCodec.sparse_encode_commit`` preserves the EF invariant
  by decoding *what the row actually carries* (tiles dropped by the static
  capacity re-enter through error feedback, like top-k dropped lanes).
* ``CommitCodec`` — the format object carried by ``DuDeEngine``.  Its
  ``encode_commit`` implements the error-feedback commit: the codec quantizes
  ``target = g + ef`` and stores the *quantized row itself* in the slab, so
  the server's ``g_workers`` row is bit-identical to what was decoded into
  ``g_bar`` — the incremental-aggregation invariant
  ``g_bar == mean_i dec(g_workers[i])`` holds exactly, with zero
  re-quantization error.

EF bitwise invariant.  With ``(q, s) = quantize(target)`` and
``dec = dequantize(q, s)``, the new residual ``ef' = target - dec`` satisfies
``dec + ef' == target`` **bitwise** in f32.  Two ingredients: (1) ``dec`` is
the EXACT real product ``q * s`` (power-of-two scale — no multiply rounding,
so even an FMA-contracted ``target - q*s`` computes the same value); (2) the
subtraction ``target - dec`` is itself exact — when ``q == 0`` trivially
(``dec == 0``), and when ``|q| >= 1`` ``target`` and ``dec`` are within a
factor of 2 of each other (``|target - dec| <= s/2 <= |dec|/2``), so the
Sterbenz lemma applies.  Hence ``dec ⊕ ef' == g ⊕ ef`` (f32 adds) holds
bit-for-bit — the decoded stream plus residual telescopes to the true stream
with no float slop.  Tested in ``tests/test_compression.py``.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import NamedTuple, Optional

import numpy as np
from jax import lax
import jax.numpy as jnp

from .flatten import PAD_MULTIPLE

__all__ = [
    "COMMIT_FORMATS", "TILE", "CommitCodec", "SparseRow",
    "quantize", "dequantize", "topk_mask", "ef_encode", "ef_decode",
    "touched_tiles", "sparse_encode", "sparse_decode_q", "sparse_decode",
    "sparse_wire_nbytes", "zero_tile_scale", "commit_digest",
]

TILE = PAD_MULTIPLE  # 128 lanes per scale tile — the engine pad granularity

COMMIT_FORMATS = ("f32", "int8_ef", "topk_ef")

_SCALE_FLOOR = 1e-12


def _tiles(x: jnp.ndarray, tile: int) -> jnp.ndarray:
    """View ``[..., P]`` (P % tile == 0) as ``[..., P//tile, tile]``."""
    if x.shape[-1] % tile:
        raise ValueError(
            f"trailing dim {x.shape[-1]} is not a multiple of tile={tile}"
        )
    return x.reshape(x.shape[:-1] + (x.shape[-1] // tile, tile))


def _pow2_ceil(x: jnp.ndarray) -> jnp.ndarray:
    """Smallest power of two >= x (x strictly positive, normal f32).

    Bit-level and branch-free: adding ``0x007FFFFF`` carries into the
    exponent iff any mantissa bit is set, and masking to the exponent field
    clears the mantissa — exact powers of two pass through unchanged.  No
    libm (``log2``/``exp2``) rounding anywhere, so eager, jit, and the
    Pallas kernel all agree bit-for-bit.
    """
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return lax.bitcast_convert_type((bits + 0x007FFFFF) & 0x7F800000,
                                    jnp.float32)


def _tile_scale(xt: jnp.ndarray) -> jnp.ndarray:
    """Per-tile quantization scale of ``[..., T, tile]`` tiles: the smallest
    POWER OF TWO >= ``max|tile| / 127`` (floored at 1e-12 so all-zero tiles
    encode to q=0)."""
    raw = jnp.maximum(jnp.max(jnp.abs(xt), axis=-1), _SCALE_FLOOR) / 127.0
    return _pow2_ceil(raw)


def quantize(x: jnp.ndarray, tile: int = TILE) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Tiled symmetric int8: ``[..., P] -> (q int8 [..., P], scale f32 [..., P//tile])``.

    Each 128-lane tile gets its own f32 scale: the smallest power of two
    >= ``max|tile| / 127`` (floored at 1e-12 so all-zero tiles encode to
    q=0).  A power-of-two scale costs at most one extra bit of quantization
    error (error <= scale/2 <= max|tile|/127) and buys EXACTNESS: ``q/scale``
    divides and ``q*scale`` multiplies without rounding, so ``dequantize`` is
    bit-deterministic under any compiler fusion (an FMA contraction of
    ``q*scale`` into a neighboring subtract cannot change the value) — the
    foundation of the bitwise EF invariant (module docstring).  The trailing
    dim must be a multiple of ``tile`` — engine slabs always are; pad shorter
    vectors with zeros first (zero lanes quantize to zero exactly).
    """
    xt = _tiles(x.astype(jnp.float32), tile)
    scale = _tile_scale(xt)
    q = jnp.clip(jnp.round(xt / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray,
               tile: int = TILE) -> jnp.ndarray:
    """Inverse of :func:`quantize`: ``q [..., P], scale [..., P//tile] -> f32 [..., P]``."""
    qt = _tiles(q.astype(jnp.float32), tile)
    return (qt * scale[..., None]).reshape(q.shape)


def topk_mask(x: jnp.ndarray, k: int, tile: int = TILE) -> jnp.ndarray:
    """Zero all but the ``k`` largest-|x| lanes of each 128-lane tile.

    Deterministic selection rule: EXACTLY ``k`` lanes survive per tile — the
    ``k`` largest by ``|x|``, with equal-magnitude ties broken toward the
    LOWER lane index.  The historical threshold sweep (``|x| >= k-th
    largest``) could keep extra lanes on exact ties and, worse, pick
    different survivors under XLA vs the Pallas lowering; this version runs
    ``k`` max-then-lowest-index selection sweeps built only from
    max/min/compare/where — ops that lower bit-identically everywhere — so
    the survivor set is a pure function of the tile values on every backend.
    The exact-k invariant is also what lets ``SparseRow`` carry a fixed
    ``k``-slot survivor list per touched tile with no overflow.
    """
    if not 1 <= k <= tile:
        raise ValueError(f"topk k={k} must be in [1, {tile}]")
    xt = _tiles(x, tile)
    a = jnp.abs(xt.astype(jnp.float32))
    lane = lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    cur = a
    keep = None
    for _ in range(k):
        m = jnp.max(cur, axis=-1, keepdims=True)
        cand = jnp.where(cur == m, lane, tile)   # lowest lane among maxima
        sel = jnp.min(cand, axis=-1, keepdims=True)
        hit = lane == sel
        keep = hit if keep is None else keep | hit
        cur = jnp.where(hit, -jnp.inf, cur)
    # select in the tiled view and reshape the values, not the bool mask:
    # Mosaic cannot reshape (or build a constant of) an i1 tile vector
    return jnp.where(keep, xt, jnp.zeros_like(xt)).reshape(x.shape)


def ef_encode(x: jnp.ndarray, err: jnp.ndarray,
              tile: int = TILE) -> tuple[tuple[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Quantize ``x + err`` and return ``((q, scale), new_err)``."""
    target = x.astype(jnp.float32) + err
    q, scale = quantize(target, tile)
    new_err = target - dequantize(q, scale, tile)
    return (q, scale), new_err


def ef_decode(q: jnp.ndarray, scale: jnp.ndarray,
              tile: int = TILE) -> jnp.ndarray:
    return dequantize(q, scale, tile)


# --------------------------------------------------- sparse wire transport

def zero_tile_scale() -> jnp.ndarray:
    """The scale every all-zero tile quantizes to: ``pow2_ceil(1e-12/127)``.

    ``quantize`` floors ``max|tile|`` at ``_SCALE_FLOOR``, so a zero tile
    always encodes to ``(q=0, scale=zero_tile_scale())`` — deterministic,
    which is what lets ``sparse_decode_q`` reconstruct the dense scale row
    bit-exactly without shipping scales for untouched tiles.
    """
    return _pow2_ceil(jnp.float32(_SCALE_FLOOR / 127.0))


class SparseRow(NamedTuple):
    """Index-carrying wire encoding of ONE ``topk_ef`` row.

    Static capacity ``cap`` touched-tile slots (the leading dim of every
    field), each carrying up to ``k`` survivors.  Live slots list their
    128-lane tile id in ascending order; pad slots use the out-of-range
    sentinel ``tiles == n_tiles(P)`` and pad survivor entries inside a live
    tile use ``lanes == 128`` — both are dropped by ``mode="drop"``
    scatters, so decode never needs the live count (it rides along for byte
    accounting and tests).  Wire cost is ``cap * (2k + 8) + 4`` bytes —
    O(k * tiles_touched) once ``cap`` is sized to the touched set, vs
    O(P) for the dense ``(q, scale)`` pair.
    """

    tiles: jnp.ndarray   # i32 [cap]     touched tile ids, ascending; pad = T
    lanes: jnp.ndarray   # u8  [cap, k]  in-tile survivor lane; pad = 128
    vals: jnp.ndarray    # i8  [cap, k]  survivor int8 payload; pad = 0
    scales: jnp.ndarray  # f32 [cap]     per-touched-tile pow-2 scale; pad = 0
    count: jnp.ndarray   # i32 []        live slots (<= cap)


def sparse_wire_nbytes(row: SparseRow) -> int:
    """Actual bytes of one ``SparseRow`` on the wire (static, cap-sized)."""
    return sum(int(x.size) * x.dtype.itemsize for x in row)


def commit_digest(*arrays) -> str:
    """Canonical 8-hex-char digest of a commit's payload arrays.

    CRC32 over each array's little-endian bytes, tagged with dtype and shape
    so byte-identical buffers of different layouts cannot collide by
    accident.  This is the per-arrival integrity stamp the multi-host
    transport sends with every commit and the trace records (schema >= 2):
    a replay recomputing the same gradients produces the same digests, so a
    digest mismatch localizes WHICH arrival diverged (or which frame was
    corrupted in flight) instead of only failing the final-params check.
    Accepts jax or numpy arrays (device arrays are pulled to host — call it
    on values the host already owns on hot paths).
    """
    crc = 0
    for x in arrays:
        a = np.asarray(x)
        a = a.astype(a.dtype.newbyteorder("<"), copy=False)
        tag = f"{a.dtype.str}{a.shape}".encode()
        crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), zlib.crc32(tag, crc))
    return f"{crc & 0xFFFFFFFF:08x}"


def touched_tiles(q: jnp.ndarray, tile: int = TILE) -> jnp.ndarray:
    """Per-tile any-nonzero bitmap: ``q [..., P] -> int8 0/1 [..., P//tile]``
    (the storage dtype of the engine's bitmaps).

    Built from ``min(max |q|, 1)`` in f32, with no bool vector anywhere:
    Mosaic cannot lower ``any(q != 0)`` over the tile axis nor relayout the
    i1 result, and this runs inside the sparse round kernel."""
    a = jnp.abs(_tiles(q, tile).astype(jnp.float32))
    return jnp.minimum(jnp.max(a, axis=-1), 1.0).astype(jnp.int8)


def sparse_encode(q: jnp.ndarray, scale: jnp.ndarray, cap: int, k: int,
                  include: Optional[jnp.ndarray] = None,
                  tile: int = TILE) -> SparseRow:
    """Dense ``(q int8 [P], scale f32 [P//tile])`` -> ``SparseRow``.

    A tile is listed iff it has any nonzero payload lane, or ``include``
    (an optional ``[P//tile]`` bool) marks it — the caller's "clear set":
    tiles the receiver currently holds nonzero for this row and that must
    be explicitly overwritten with zeros.  Tiles beyond the static ``cap``
    are dropped lowest-tile-id-first-kept; callers recover the loss through
    error feedback (``CommitCodec.sparse_encode_commit`` decodes what the
    row actually carries).  Requires <= ``k`` nonzero lanes per tile
    (``topk_mask``'s exact-k rule guarantees it); extra lanes are dropped.
    """
    t = q.shape[-1] // tile
    if not 1 <= cap <= t:
        raise ValueError(f"sparse cap={cap} outside [1, {t}]")
    qt = _tiles(q, tile)                                    # [T, tile]
    touched = jnp.any(qt != 0, axis=-1)
    if include is not None:
        touched = touched | include.astype(bool)
    slot = jnp.where(touched, jnp.cumsum(touched.astype(jnp.int32)) - 1, cap)
    slot = jnp.minimum(slot, cap)              # overflow tiles -> dropped
    tids = jnp.arange(t, dtype=jnp.int32)
    tiles = jnp.full((cap,), t, jnp.int32).at[slot].set(tids, mode="drop")
    count = jnp.minimum(jnp.sum(touched.astype(jnp.int32)), cap)

    live = tiles < t
    src = jnp.minimum(tiles, t - 1)            # clamp pads for a safe gather
    qrow = jnp.where(live[:, None], qt[src], jnp.int8(0))   # [cap, tile]
    srow = jnp.where(live, scale[src], jnp.float32(0.0))    # [cap]

    nz = qrow != 0
    lidx = lax.broadcasted_iota(jnp.int32, nz.shape, 1)
    rows = lax.broadcasted_iota(jnp.int32, nz.shape, 0)
    lslot = jnp.where(nz, jnp.cumsum(nz.astype(jnp.int32), axis=-1) - 1, k)
    lanes = jnp.full((cap, k), tile, jnp.uint8).at[rows, lslot].set(
        lidx.astype(jnp.uint8), mode="drop")
    vals = jnp.zeros((cap, k), jnp.int8).at[rows, lslot].set(
        qrow, mode="drop")
    return SparseRow(tiles, lanes, vals, srow, count)


def sparse_decode_q(row: SparseRow, p: int,
                    tile: int = TILE) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``SparseRow -> (q int8 [P], scale f32 [P//tile])`` — the dense pair.

    Bit-exact inverse of ``sparse_encode`` whenever the touched set fit in
    ``cap`` and each tile had <= k survivors: unlisted tiles come back as
    ``(q=0, scale=zero_tile_scale())``, exactly what ``quantize`` emits for
    a zero tile.  Oracle/test path — the engine's slab fold scatters the
    row directly instead (``DuDeEngine.sparse_fold``).
    """
    t = p // tile
    cap, k = row.lanes.shape
    rows = lax.broadcasted_iota(jnp.int32, (cap, k), 0)
    tile_img = jnp.zeros((cap, tile), jnp.int8).at[
        rows, row.lanes.astype(jnp.int32)].set(row.vals, mode="drop")
    qt = jnp.zeros((t, tile), jnp.int8).at[row.tiles].set(
        tile_img, mode="drop")
    scale = jnp.full((t,), zero_tile_scale(), jnp.float32).at[row.tiles].set(
        row.scales, mode="drop")
    return qt.reshape(p), scale


def sparse_decode(row: SparseRow, p: int, tile: int = TILE) -> jnp.ndarray:
    """``SparseRow -> f32 [P]`` decoded values, via a direct survivor
    scatter (``val * scale`` is exact — power-of-two scales), with no dense
    int8 intermediate."""
    t = p // tile
    dec = (row.vals.astype(jnp.float32)
           * row.scales[:, None].astype(jnp.float32))          # [cap, k]
    lanes = row.lanes.astype(jnp.int32)
    pos = row.tiles[:, None] * tile + lanes
    pos = jnp.where((lanes < tile) & (row.tiles[:, None] < t), pos, p)
    return jnp.zeros((p,), jnp.float32).at[pos].set(dec, mode="drop")


@dataclasses.dataclass(frozen=True)
class CommitCodec:
    """Commit/storage format for the flat engine's ``[n, P]`` slabs.

    ``f32``      — today's format: full-precision rows, no EF slot.
    ``int8_ef``  — tiled symmetric int8 rows + per-tile f32 scales, with a
                   ``[P]`` error-feedback residual on the commit stream.
    ``topk_ef``  — per-tile magnitude top-k applied before int8 quantization;
                   same slab layout (the int8 payload is mostly zeros, the
                   wire payload is k values + k in-tile indices per tile).
    """

    format: str = "f32"
    tile: int = TILE
    topk: int = 16  # survivors per tile (topk_ef only)

    def __post_init__(self):
        if self.format not in COMMIT_FORMATS:
            raise ValueError(
                f"commit_format {self.format!r} not in {COMMIT_FORMATS}"
            )
        if not 1 <= self.topk <= self.tile:
            raise ValueError(f"topk={self.topk} must be in [1, {self.tile}]")

    @property
    def compressed(self) -> bool:
        return self.format != "f32"

    def n_tiles(self, p: int) -> int:
        if p % self.tile:
            raise ValueError(f"P={p} not a multiple of tile={self.tile}")
        return p // self.tile

    # ------------------------------------------------------------- codec ops

    def sparsify(self, x: jnp.ndarray) -> jnp.ndarray:
        """The pre-quantization lane filter (identity except topk_ef)."""
        if self.format == "topk_ef":
            return topk_mask(x, self.topk, self.tile)
        return x

    def encode(self, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """``[..., P] -> (q, scale)`` (sparsify then tiled int8)."""
        return quantize(self.sparsify(x), self.tile)

    def decode(self, q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
        return dequantize(q, scale, self.tile)

    def encode_commit(
        self, g: jnp.ndarray, ef: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Error-feedback commit encode of one ``[P]`` gradient row.

        Returns ``(q, scale, dec, ef_new)`` where ``dec = decode(q, scale)``
        and ``dec + ef_new == g + ef`` bitwise (see module docstring).
        """
        target = g.astype(jnp.float32) + ef
        q, scale = self.encode(target)
        dec = self.decode(q, scale)
        return q, scale, dec, target - dec

    # ------------------------------------------------------ sparse transport

    def _require_sparse(self):
        if self.format != "topk_ef":
            raise ValueError(
                f"SparseRow transport needs commit_format='topk_ef', "
                f"not {self.format!r} (other formats have dense payloads)")

    def sparse_cap(self, p: int, cap: Optional[int] = None) -> int:
        """Resolve a static touched-tile capacity (None = all tiles)."""
        self._require_sparse()
        t = self.n_tiles(p)
        if cap is None:
            return t
        if not 1 <= cap <= t:
            raise ValueError(f"sparse cap={cap} outside [1, {t}]")
        return cap

    def encode_sparse(self, x: jnp.ndarray, cap: Optional[int] = None,
                      include: Optional[jnp.ndarray] = None) -> SparseRow:
        """``[P] -> SparseRow`` (topk sparsify, tiled int8, index-carrying)."""
        cap = self.sparse_cap(x.shape[-1], cap)
        q, s = self.encode(x)
        return sparse_encode(q, s, cap, self.topk, include=include,
                             tile=self.tile)

    def sparse_encode_commit(
        self, g: jnp.ndarray, ef: jnp.ndarray, cap: Optional[int] = None,
        include: Optional[jnp.ndarray] = None,
    ) -> tuple[SparseRow, jnp.ndarray]:
        """Error-feedback commit encode of one ``[P]`` gradient row into a
        ``SparseRow``.  Returns ``(row, ef_new)``.

        The residual is computed against the decode of WHAT THE ROW
        CARRIES — so the bitwise EF invariant ``dec(row) + ef_new == g + ef``
        holds even when the static ``cap`` drops touched tiles (their full
        target re-enters EF, exactly like top-k dropped lanes).  When
        nothing is dropped this matches ``encode_commit`` bit-for-bit.
        """
        cap = self.sparse_cap(g.shape[-1], cap)
        target = g.astype(jnp.float32) + ef
        q, scale = self.encode(target)
        row = sparse_encode(q, scale, cap, self.topk, include=include,
                            tile=self.tile)
        dec = sparse_decode(row, target.shape[-1], self.tile)
        return row, target - dec

    def quant_bound(self, x: jnp.ndarray) -> jnp.ndarray:
        """Per-tile worst-case |dequantize(quantize(x)) - x| bound: scale/2 + slop.

        Rounding to the nearest int8 level is off by at most ``scale/2`` per
        lane — exactly, because the power-of-two scale makes the divide and
        multiply exact; the small extra term covers the one case where the
        floored ``max/127`` rounds a hair low and a max-magnitude lane clips
        at 127.  Since ``scale < 2 * max|tile|/127``, the bound is at most
        the classic ``max|tile|/127`` (+ slop).  (For ``topk_ef`` this bounds
        the error on *surviving* lanes; dropped lanes carry their full value
        into EF.)
        """
        xs = self.sparsify(x)
        scale = _tile_scale(_tiles(xs.astype(jnp.float32), self.tile))
        return scale * (0.5 + 4.0 * jnp.finfo(jnp.float32).eps * 127.0)

    # ----------------------------------------------------------- byte models

    def commit_wire_bytes(self, p: int,
                          tiles_touched: Optional[int] = None) -> int:
        """Bytes one per-arrival commit moves over the wire.

        ``tiles_touched`` (topk_ef only) switches to the real ``SparseRow``
        payload: per listed tile, k int8 values + k uint8 lane indices + one
        f32 scale + one i32 tile id, plus the i32 live count — O(k *
        tiles_touched) instead of the dense row's O(P).  ``None`` keeps the
        historical dense-row model (every tile shipped, positions implicit).
        """
        t = self.n_tiles(p)
        if self.format == "f32":
            return 4 * p
        if self.format == "int8_ef":
            return p + 4 * t               # int8 payload + f32 scale per tile
        if tiles_touched is not None:
            self._require_sparse()
            if not 0 <= tiles_touched <= t:
                raise ValueError(
                    f"tiles_touched={tiles_touched} outside [0, {t}]")
            return tiles_touched * (2 * self.topk + 8) + 4
        # dense topk_ef row: k (value int8 + in-tile index uint8) per tile
        # + scales
        return t * 2 * self.topk + 4 * t

    def slab_bytes(self, n: int, p: int) -> int:
        """Resident bytes of one ``[n, P]`` worker slab (+ its scale slab)."""
        if self.format == "f32":
            return 4 * n * p
        return n * p + 4 * n * self.n_tiles(p)
