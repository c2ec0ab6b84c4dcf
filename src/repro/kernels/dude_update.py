"""Pallas TPU kernel: fused DuDe-ASGD server round on flat parameter tiles.

The server hot loop (paper Alg. 1 lines 4-6 + the semi-async variant) is a
pure streaming op over Theta(n * p) buffer state: per round it must
  commit:  g_bar += sum_i cm_i * (inflight_i - G~_i) / n ;  G~_i <- inflight_i
  latch:   inflight_i <- fresh_i  (where start_i)
  apply:   w <- w - eta * g^t      (plus optimizer slot streams, see below)
Arithmetic intensity is O(1) flops/byte => HBM-bandwidth-bound, so the win is
FUSION: one pass over the streams instead of the ~9 separate elementwise
HLO ops XLA emits, plus no intermediate materialization.

The apply is not limited to plain SGD: ``dude_round_apply_pallas`` streams
the optimizer slot slabs (momentum ``m``, AdamW ``{m, v}`` — flat ``[P]``
vectors in the same segment-range layout as ``g_bar``) through the same
single pass, computing the slot update and the parameter step tile-by-tile.
The optimizer math mirrors ``optim.transforms.FlatOptimizer.update``
op-for-op, so the fused path is bit-exact against the unfused flat apply.
AdamW's bias corrections depend only on the (replicated) step counter, so
the caller computes them once and passes two scalars in.

Grid: (lane tiles of the flattened parameter vector, worker-row groups).
Each program instance owns a [ROWS, TILE] block of the stacked buffers and
a [TILE] slice of g_bar/params/slots in VMEM.  On the TPU a group is 8
worker rows whenever 8 divides n, so neither VMEM nor the kernel body grows
with n; the worker sum is carried across the row groups in a VMEM
accumulator and g_bar/params/slots are written at the last group.  In
interpret mode one group holds all n rows, which keeps the sum's order
(and so every CPU result) bit-for-bit that of the plain-jnp backends.  The
engine sizes TILE with ``derive_tile`` from n, the slab dtype and the
stream count, so that every block, double-buffered, and the body's f32
temporaries fit ``VMEM_BUDGET``; the last block may be ragged (TILE need
not divide P).

Compressed slabs (``dude_round_apply_q_pallas``): when the engine's
``commit_format`` is ``int8_ef``/``topk_ef`` the worker slabs are stored as
int8 payloads + per-128-lane-tile f32 scale rows (``core/compression.py``).
The quantized kernel streams q-rows and scale rows through the same single
pass, dequantizing both slabs in VMEM, folding the commit delta in f32,
copying committed rows quantized (no re-quantization), and quantizing the
fresh latch rows in-kernel — cutting the dominant slab traffic ~4x.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 16384  # f32 elements per program instance per stream row

# Legal block widths on the TPU: a 1-D f32 block is laid out in 1024-lane
# tiles, and the compressed kernels' [n, tile/128] scale blocks need a
# multiple of 128 lanes, i.e. tile % (128 * 128) == 0.
LANE_QUANTUM = 1024
Q_LANE_QUANTUM = 128 * 128
# VMEM one grid step is sized to (v5e: 128 MiB per core, 16 MiB of it
# scoped by default).  Each kernel asks the compiler for twice its own
# estimate, at least 32 MiB and at most 100 MiB.
VMEM_BUDGET = 16 * 2**20

# slot streams per optimizer kind: () | ("m",) | ("m", "v")
SLOT_STREAMS = {"sgd": 0, "momentum": 1, "adamw": 2}


def _row_block(n: int, interpret: bool) -> int:
    """Worker rows per grid step: 8 on the TPU when 8 divides n, else all
    n (a block's row count must be a multiple of 8 or the whole axis).
    Interpret mode keeps one group, so the worker sum keeps the plain-jnp
    order; tests force 8 here to run the TPU's multi-group grid."""
    return 8 if not interpret and n % 8 == 0 else n


def _padded_rows(n: int, itemsize: int) -> int:
    """Rows an ``[n, T]`` block takes in VMEM: a sublane tile holds 8
    32-bit rows, packed 16 bf16 or 32 int8 rows deep."""
    per = 32 // itemsize
    return -(-n // per) * per


def _vmem_bytes_per_lane(rows: int, slab_itemsize: int, n_slots: int,
                        compressed: bool) -> int:
    """VMEM one lane of a grid step holds: every in/out block double-
    buffered, the worker-sum accumulator, and the kernel body's f32
    ``[rows, T]`` temporaries (dequantized slabs and the top-k sweeps double
    them under a compressed format)."""
    r32 = _padded_rows(rows, 4) * 4
    slab = _padded_rows(rows, slab_itemsize) * slab_itemsize
    # fresh in; g_workers, inflight in + out; g_bar, w, slots in + out
    blocks = r32 + 4 * slab + (4 + 2 * n_slots) * 4
    temps = 4 * r32 + 4
    if compressed:
        blocks += 8 * r32 // 128   # scale / bitmap rows, in + out
        temps = 8 * r32 + 4
    return 2 * blocks + temps


def derive_tile(P: int, n: int, slab_itemsize: int, n_slots: int,
                compressed: bool) -> int:
    """The widest legal TPU tile whose grid step fits ``VMEM_BUDGET``
    (never below one lane quantum); ``P`` itself when one block covers it."""
    q = Q_LANE_QUANTUM if compressed else LANE_QUANTUM
    per_lane = _vmem_bytes_per_lane(_row_block(n, False), slab_itemsize,
                                   n_slots, compressed)
    fit = max(q, VMEM_BUDGET // per_lane // q * q)
    return P if P <= fit else fit


def kernel_grid(P: int, n: int, tile: int, interpret: bool) -> tuple:
    """The fused kernels' grid: (lane blocks, worker-row groups)."""
    return (pl.cdiv(P, tile), n // _row_block(n, interpret))


class _Specs(NamedTuple):
    """The fused kernels' blocks on the (lane tile, row group) grid."""
    row: Any    # [rows, T] worker-slab block
    srow: Any   # [rows, T/128] scale / bitmap block
    vec: Any    # [T] g_bar / params / slot block
    mask: Any   # [rows, 1] commit / start mask column
    sc2: Any    # [2] AdamW bias corrections
    smem: Any   # whole array in SMEM (per-block flags)


def _block_specs(rows: int, tile: int) -> _Specs:
    return _Specs(
        row=pl.BlockSpec((rows, tile), lambda i, r: (r, i)),
        srow=pl.BlockSpec((rows, tile // 128), lambda i, r: (r, i)),
        vec=pl.BlockSpec((tile,), lambda i, r: (i,)),
        mask=pl.BlockSpec((rows, 1), lambda i, r: (r, 0)),
        sc2=pl.BlockSpec((2,), lambda i, r: (0,)),
        smem=pl.BlockSpec(memory_space=pltpu.SMEM),
    )


def _pallas_call(kernel, *, n: int, P: int, tile: int, rows: int,
                 interpret: bool, slab_itemsize: int, n_slots: int,
                 compressed: bool, in_specs, out_specs, out_shape,
                 first_alias: int):
    """``pallas_call`` on the (lane tile, row group) grid, with the
    worker-sum accumulator when there is more than one row group, the
    in-place aliases, and a VMEM limit of twice the step's estimate."""
    grid = (pl.cdiv(P, tile), n // rows)
    est = _vmem_bytes_per_lane(rows, slab_itemsize, n_slots,
                              compressed) * tile
    limit = min(max(2 * est, 32 * 2**20), 100 * 2**20)
    return pl.pallas_call(
        functools.partial(kernel, groups=grid[1]),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([pltpu.VMEM((tile,), jnp.float32)] if grid[1] > 1
                        else []),
        input_output_aliases=_aliases(first_alias, len(out_shape)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(limit)),
        interpret=interpret)


def _fold_groups(part, acc_ref, finish):
    """Sum ``part`` (this row group's share of the worker sum) over the row
    groups and call ``finish(total)`` once, at the last group.  With one
    group there is no accumulator and ``finish`` sees ``part`` itself."""
    if acc_ref is None:
        finish(part)
        return
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _():
        acc_ref[...] = part

    @pl.when(r > 0)
    def _():
        acc_ref[...] += part

    @pl.when(r == pl.num_programs(1) - 1)
    def _():
        finish(acc_ref[...])


def _mask_col(mask: jnp.ndarray) -> jnp.ndarray:
    """``[n]`` bool/float mask -> the ``[n, 1]`` f32 column the kernels read.

    A 1-D ``[n]`` block broadcast as ``m[:, None]`` inside the kernel is a
    ``vector<n x i1> -> vector<n x 1 x i1>`` shape cast that Mosaic refuses;
    a 2-D column block broadcasts against ``[n, T]`` rows without one."""
    return mask.astype(jnp.float32).reshape(-1, 1)


def _aliases(first_in: int, n_out: int) -> dict:
    """Every output overwrites the input stream it updates in place: the
    outputs are, in order, the inputs from ``first_in`` on.  Without this
    the step holds old and new ``[n, P]`` slabs at once."""
    return {first_in + j: j for j in range(n_out)}


def _opt_apply(g, w_ref, slot_refs, bc_ref, w_out, slot_outs,
               kind: str, hp: dict):
    """Fused optimizer tail shared by the f32 and quantized round kernels.

    Mirrors ``optim.transforms.FlatOptimizer.update`` op-for-op so the fused
    path stays bit-exact against the unfused flat apply.
    """
    w = w_ref[...]
    if kind == "sgd":
        w_out[...] = w - hp["lr"] * g
    elif kind == "momentum":
        (m_ref,) = slot_refs
        m = hp["beta"] * m_ref[...] + g
        d = hp["beta"] * m + g if hp["nesterov"] else m
        w_out[...] = w - hp["lr"] * d
        slot_outs[0][...] = m
    elif kind == "adamw":
        m_ref, v_ref = slot_refs
        b1, b2 = hp["b1"], hp["b2"]
        m = b1 * m_ref[...] + (1 - b1) * g
        v = b2 * v_ref[...] + (1 - b2) * jnp.square(g)
        bc = bc_ref[...]
        bc1, bc2 = bc[0], bc[1]
        step = (m / bc1) / (jnp.sqrt(v / bc2) + hp["eps"]) \
            + hp["weight_decay"] * w
        w_out[...] = w - hp["lr"] * step
        slot_outs[0][...] = m
        slot_outs[1][...] = v
    else:
        raise ValueError(f"unknown optimizer kind {kind!r}")


def _split_refs(refs, n_in: int, n_out: int, groups: int):
    """(inputs, outputs, accumulator or None) of a kernel's refs."""
    acc = refs[n_in + n_out] if groups > 1 else None
    return refs[:n_in], refs[n_in:n_in + n_out], acc


def _round_apply_kernel(*refs, n_workers: int, kind: str, hp: tuple,
                        groups: int):
    """One [ROWS, TILE] block: DuDe round + fused optimizer apply.

    refs layout (in): cm[R,1], sm[R,1], fresh[R,T], gw[R,T], infl[R,T],
    gbar[T], w[T], slots*[T], (bc[2] for adamw); (out): gw, infl, gbar, w,
    slots*; (scratch, groups > 1): acc[T].
    """
    hp = dict(hp)
    n_slots = SLOT_STREAMS[kind]
    n_in = 7 + n_slots + (1 if kind == "adamw" else 0)
    ins, outs, acc_ref = _split_refs(refs, n_in, 4 + n_slots, groups)
    (cm_ref, sm_ref, fresh_ref, gw_ref, infl_ref, gbar_ref, w_ref,
     *rest_in) = ins
    gw_out, infl_out, gbar_out, w_out, *slot_outs = outs

    cm = cm_ref[...]                       # [R, 1] f32
    sm = sm_ref[...] > 0                   # [R, 1]
    fresh = fresh_ref[...].astype(jnp.float32)   # [R, T]
    gw = gw_ref[...].astype(jnp.float32)         # [R, T]
    infl = infl_ref[...].astype(jnp.float32)     # [R, T]

    delta = cm * (infl - gw)
    gw_new = jnp.where(cm > 0, infl, gw)
    infl_new = jnp.where(sm, fresh, infl)
    gw_out[...] = gw_new.astype(gw_out.dtype)
    infl_out[...] = infl_new.astype(infl_out.dtype)

    slot_refs = rest_in[:n_slots]
    bc_ref = rest_in[n_slots] if kind == "adamw" else None

    def finish(total):
        g = gbar_ref[...] + total / n_workers
        gbar_out[...] = g
        _opt_apply(g, w_ref, slot_refs, bc_ref, w_out, slot_outs, kind, hp)

    _fold_groups(jnp.sum(delta, axis=0), acc_ref, finish)


def _round_apply_q_kernel(*refs, n_workers: int, kind: str, hp: tuple,
                          fmt: str, topk: int, groups: int):
    """Quantized-slab twin of ``_round_apply_kernel``.

    The ``[n, T]`` worker slabs arrive as int8 payloads plus per-128-lane-tile
    f32 scale rows ``[n, T/128]``; dequantization of both slabs and the int8
    latch quantization of the fresh rows are fused into the same single pass.
    Committed rows copy the *quantized* in-flight payload (q + scale) verbatim
    — no re-quantization — so the incremental invariant
    ``g_bar == mean_i dec(g_workers[i])`` is preserved exactly.  The codec
    math is the shared ``core.compression`` ops, so this kernel is
    bit-identical to the plain-jnp reference/indexed twins.

    refs layout (in): cm[R,1], sm[R,1], fresh[R,T], gw_q[R,T]i8,
    gw_s[R,T/128], in_q[R,T]i8, in_s[R,T/128], gbar[T], w[T], slots*[T],
    (bc[2] for adamw); (out): gw_q, gw_s, in_q, in_s, gbar, w, slots*;
    (scratch, groups > 1): acc[T].
    """
    from ..core.compression import dequantize, quantize, topk_mask

    hp = dict(hp)
    n_slots = SLOT_STREAMS[kind]
    n_in = 9 + n_slots + (1 if kind == "adamw" else 0)
    ins_refs, outs, acc_ref = _split_refs(refs, n_in, 6 + n_slots, groups)
    (cm_ref, sm_ref, fresh_ref, gwq_ref, gws_ref, inq_ref, ins_ref,
     gbar_ref, w_ref, *rest_in) = ins_refs
    gwq_out, gws_out, inq_out, ins_out, gbar_out, w_out, *slot_outs = outs

    cm = cm_ref[...]                       # [R, 1] f32
    sm = sm_ref[...] > 0                   # [R, 1]
    fresh = fresh_ref[...].astype(jnp.float32)   # [R, T]
    gwq, gws = gwq_ref[...], gws_ref[...]
    inq, ins = inq_ref[...], ins_ref[...]

    gw = dequantize(gwq, gws)
    infl = dequantize(inq, ins)
    delta = cm * (infl - gw)

    commit = cm > 0
    gwq_out[...] = jnp.where(commit, inq, gwq)
    gws_out[...] = jnp.where(commit, ins, gws)

    latch = topk_mask(fresh, topk) if fmt == "topk_ef" else fresh
    qf, sf = quantize(latch)
    inq_out[...] = jnp.where(sm, qf, inq)
    ins_out[...] = jnp.where(sm, sf, ins)

    slot_refs = rest_in[:n_slots]
    bc_ref = rest_in[n_slots] if kind == "adamw" else None

    def finish(total):
        g = gbar_ref[...] + total / n_workers
        gbar_out[...] = g
        _opt_apply(g, w_ref, slot_refs, bc_ref, w_out, slot_outs, kind, hp)

    _fold_groups(jnp.sum(delta, axis=0), acc_ref, finish)


def dude_round_apply_pallas(
    commit_mask: jnp.ndarray,   # [n] bool
    start_mask: jnp.ndarray,    # [n] bool
    fresh: jnp.ndarray,         # [n, P] fresh gradients (live model)
    g_workers: jnp.ndarray,     # [n, P] buffer dtype
    inflight: jnp.ndarray,      # [n, P] buffer dtype
    g_bar: jnp.ndarray,         # [P] f32
    w: jnp.ndarray,             # [P] f32 flat master params
    slots: tuple = (),          # optimizer slot slabs, each [P] f32
    bias_corr: jnp.ndarray | None = None,  # [2] f32 (adamw only)
    *,
    kind: str = "sgd",
    hp: tuple = (("lr", 0.0),),
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
):
    """Fused round + optimizer apply.  Returns
    ``(g_workers', inflight', g_bar', w', slots')``."""
    n, P = fresh.shape
    assert g_workers.shape == (n, P) and inflight.shape == (n, P)
    assert g_bar.shape == (P,) and w.shape == (P,)
    n_slots = SLOT_STREAMS[kind]
    assert len(slots) == n_slots, (kind, len(slots))
    assert all(s.shape == (P,) for s in slots)
    assert (bias_corr is not None) == (kind == "adamw")
    tile = min(tile, P)
    rows = _row_block(n, interpret)
    sp = _block_specs(rows, tile)

    in_specs = [sp.mask, sp.mask, sp.row, sp.row, sp.row, sp.vec, sp.vec] \
        + [sp.vec] * n_slots
    args = [_mask_col(commit_mask), _mask_col(start_mask), fresh, g_workers,
            inflight, g_bar, w] + list(slots)
    if kind == "adamw":
        in_specs.append(sp.sc2)
        args.append(bias_corr.astype(jnp.float32))

    kernel = functools.partial(_round_apply_kernel, n_workers=n, kind=kind,
                               hp=tuple(hp))
    out = _pallas_call(
        kernel, n=n, P=P, tile=tile, rows=rows, interpret=interpret,
        slab_itemsize=jnp.dtype(g_workers.dtype).itemsize, n_slots=n_slots,
        compressed=False, in_specs=in_specs,
        out_specs=[sp.row, sp.row, sp.vec, sp.vec] + [sp.vec] * n_slots,
        out_shape=[
            jax.ShapeDtypeStruct((n, P), g_workers.dtype),
            jax.ShapeDtypeStruct((n, P), inflight.dtype),
            jax.ShapeDtypeStruct((P,), jnp.float32),
            jax.ShapeDtypeStruct((P,), w.dtype),
        ] + [jax.ShapeDtypeStruct((P,), jnp.float32)] * n_slots,
        first_alias=3,
    )(*args)
    gw_new, infl_new, gbar_new, w_new = out[:4]
    return gw_new, infl_new, gbar_new, w_new, tuple(out[4:])


def dude_round_apply_q_pallas(
    commit_mask: jnp.ndarray,   # [n] bool
    start_mask: jnp.ndarray,    # [n] bool
    fresh: jnp.ndarray,         # [n, P] f32 fresh gradients (live model)
    gw_q: jnp.ndarray,          # [n, P] int8 committed-gradient payload
    gw_scale: jnp.ndarray,      # [n, P/128] f32 per-tile scales
    in_q: jnp.ndarray,          # [n, P] int8 in-flight payload
    in_scale: jnp.ndarray,      # [n, P/128] f32
    g_bar: jnp.ndarray,         # [P] f32
    w: jnp.ndarray,             # [P] f32 flat master params
    slots: tuple = (),          # optimizer slot slabs, each [P] f32
    bias_corr: jnp.ndarray | None = None,  # [2] f32 (adamw only)
    *,
    kind: str = "sgd",
    hp: tuple = (("lr", 0.0),),
    fmt: str = "int8_ef",
    topk: int = 16,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
):
    """Fused round + apply over quantized slabs.  Returns
    ``(gw_q', gw_scale', in_q', in_scale', g_bar', w', slots')``.

    Streams the int8 q-rows and their f32 scale rows through the same
    grid as the f32 kernel; each program instance additionally owns a
    ``[ROWS, tile/128]`` slice of both scale slabs.  ``tile`` must be a multiple
    of the 128-lane scale granularity (engine tiles always are; on the TPU
    of ``Q_LANE_QUANTUM``, unless one block covers P).
    """
    from ..core.compression import TILE as QTILE

    n, P = fresh.shape
    t = P // QTILE
    assert gw_q.shape == (n, P) and in_q.shape == (n, P)
    assert gw_scale.shape == (n, t) and in_scale.shape == (n, t)
    assert g_bar.shape == (P,) and w.shape == (P,)
    n_slots = SLOT_STREAMS[kind]
    assert len(slots) == n_slots, (kind, len(slots))
    assert (bias_corr is not None) == (kind == "adamw")
    tile = min(tile, P)
    assert tile % QTILE == 0, f"P={P} tile={tile}"
    rows = _row_block(n, interpret)
    sp = _block_specs(rows, tile)

    in_specs = [sp.mask, sp.mask, sp.row, sp.row, sp.srow, sp.row, sp.srow,
                sp.vec, sp.vec] + [sp.vec] * n_slots
    args = [_mask_col(commit_mask), _mask_col(start_mask),
            fresh.astype(jnp.float32), gw_q, gw_scale, in_q, in_scale,
            g_bar, w] + list(slots)
    if kind == "adamw":
        in_specs.append(sp.sc2)
        args.append(bias_corr.astype(jnp.float32))

    kernel = functools.partial(_round_apply_q_kernel, n_workers=n, kind=kind,
                               hp=tuple(hp), fmt=fmt, topk=topk)
    out = _pallas_call(
        kernel, n=n, P=P, tile=tile, rows=rows, interpret=interpret,
        slab_itemsize=1, n_slots=n_slots, compressed=True, in_specs=in_specs,
        out_specs=[sp.row, sp.srow, sp.row, sp.srow, sp.vec, sp.vec]
        + [sp.vec] * n_slots,
        out_shape=[
            jax.ShapeDtypeStruct((n, P), jnp.int8),
            jax.ShapeDtypeStruct((n, t), jnp.float32),
            jax.ShapeDtypeStruct((n, P), jnp.int8),
            jax.ShapeDtypeStruct((n, t), jnp.float32),
            jax.ShapeDtypeStruct((P,), jnp.float32),
            jax.ShapeDtypeStruct((P,), w.dtype),
        ] + [jax.ShapeDtypeStruct((P,), jnp.float32)] * n_slots,
        first_alias=3,
    )(*args)
    return out[0], out[1], out[2], out[3], out[4], out[5], tuple(out[6:])


def _round_apply_sparse_kernel(*refs, n_workers: int, kind: str, hp: tuple,
                               topk: int, groups: int):
    """Touched-tile-gated twin of ``_round_apply_q_kernel`` (topk_ef only).

    A precomputed per-block activity flag (``blk``, from the engine's
    touched-tile bitmaps: does any committing row hold nonzero payload in
    any 128-lane tile of this block?) gates the expensive part — the dual
    dequantization and the commit fold — behind ``lax.cond``.  Inactive
    blocks pass ``g_bar`` and the committed payload through untouched, which
    is value-identical to the dense kernel: untouched tiles decode to exact
    +0.0 (and ``g_bar`` entries are never -0.0 — they are only ever produced
    by ``x + delta`` chains from a +0.0 init).  Everything whose result is
    NOT recoverable from the bitmaps stays dense: the fresh latch (arbitrary
    new values), the scale-row copies (stale scales are decode-invisible but
    not bitwise-invisible, and they are 1/128 of the payload), the bitmap
    updates, and the optimizer tail.

    refs layout (in): cm[R,1], sm[R,1], blk[P/T] (SMEM), fresh[R,T],
    gw_q[R,T]i8, gw_s[R,T/128], gw_t[R,T/128]i8, in_q[R,T]i8,
    in_s[R,T/128], in_t[R,T/128]i8, gbar[T], w[T], slots*[T], (bc[2] for
    adamw); (out): gw_q, gw_s, gw_t, in_q, in_s, in_t, gbar, w, slots*;
    (scratch, groups > 1): acc[T].
    """
    from ..core.compression import (
        dequantize, quantize, topk_mask, touched_tiles,
    )

    hp = dict(hp)
    n_slots = SLOT_STREAMS[kind]
    n_in = 12 + n_slots + (1 if kind == "adamw" else 0)
    ins_refs, outs, acc_ref = _split_refs(refs, n_in, 8 + n_slots, groups)
    (cm_ref, sm_ref, blk_ref, fresh_ref, gwq_ref, gws_ref, gwt_ref,
     inq_ref, ins_ref, int_ref, gbar_ref, w_ref, *rest_in) = ins_refs
    (gwq_out, gws_out, gwt_out, inq_out, ins_out, int_out, gbar_out,
     w_out, *slot_outs) = outs

    cm = cm_ref[...]                       # [R, 1] f32
    sm = sm_ref[...] > 0                   # [R, 1]
    active = blk_ref[pl.program_id(0)] != 0
    fresh = fresh_ref[...].astype(jnp.float32)   # [R, T]
    gwq, gws, gwt = gwq_ref[...], gws_ref[...], gwt_ref[...]
    inq, ins, int_ = inq_ref[...], ins_ref[...], int_ref[...]
    commit = cm > 0

    def fold(_):
        gw = dequantize(gwq, gws)
        infl = dequantize(inq, ins)
        return (jnp.sum(cm * (infl - gw), axis=0),
                jnp.where(commit, inq, gwq))

    def skip(_):
        return jnp.zeros(gwq.shape[1:], jnp.float32), gwq

    part, gwq_new = jax.lax.cond(active, fold, skip, None)

    gwq_out[...] = gwq_new
    gws_out[...] = jnp.where(commit, ins, gws)
    gwt_out[...] = jnp.where(commit, int_, gwt)

    latch = topk_mask(fresh, topk)
    qf, sf = quantize(latch)
    inq_out[...] = jnp.where(sm, qf, inq)
    ins_out[...] = jnp.where(sm, sf, ins)
    int_out[...] = jnp.where(sm, touched_tiles(qf), int_)

    slot_refs = rest_in[:n_slots]
    bc_ref = rest_in[n_slots] if kind == "adamw" else None

    def finish(total):
        gbar = gbar_ref[...]
        g = jnp.where(active, gbar + total / n_workers, gbar)
        gbar_out[...] = g
        _opt_apply(g, w_ref, slot_refs, bc_ref, w_out, slot_outs, kind, hp)

    _fold_groups(part, acc_ref, finish)


def dude_round_apply_sparse_pallas(
    commit_mask: jnp.ndarray,   # [n] bool
    start_mask: jnp.ndarray,    # [n] bool
    blk: jnp.ndarray,           # [cdiv(P, tile)] i32 per-block activity
    fresh: jnp.ndarray,         # [n, P] f32 fresh gradients (live model)
    gw_q: jnp.ndarray,          # [n, P] int8 committed-gradient payload
    gw_scale: jnp.ndarray,      # [n, P/128] f32 per-tile scales
    gw_touched: jnp.ndarray,    # [n, P/128] int8 touched-tile bitmap
    in_q: jnp.ndarray,          # [n, P] int8 in-flight payload
    in_scale: jnp.ndarray,      # [n, P/128] f32
    in_touched: jnp.ndarray,    # [n, P/128] int8
    g_bar: jnp.ndarray,         # [P] f32
    w: jnp.ndarray,             # [P] f32 flat master params
    slots: tuple = (),          # optimizer slot slabs, each [P] f32
    bias_corr: jnp.ndarray | None = None,  # [2] f32 (adamw only)
    *,
    kind: str = "sgd",
    hp: tuple = (("lr", 0.0),),
    topk: int = 16,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
):
    """Fused round + apply over quantized slabs, folding ONLY the blocks a
    committing row touches (``topk_ef`` + touched-tile bitmaps).  Returns
    ``(gw_q', gw_scale', gw_touched', in_q', in_scale', in_touched',
    g_bar', w', slots')`` — bit-for-bit ``dude_round_apply_q_pallas`` with
    ``fmt="topk_ef"`` on the shared streams."""
    from ..core.compression import TILE as QTILE

    n, P = fresh.shape
    t = P // QTILE
    assert gw_q.shape == (n, P) and in_q.shape == (n, P)
    assert gw_scale.shape == (n, t) and in_scale.shape == (n, t)
    assert gw_touched.shape == (n, t) and in_touched.shape == (n, t)
    assert g_bar.shape == (P,) and w.shape == (P,)
    n_slots = SLOT_STREAMS[kind]
    assert len(slots) == n_slots, (kind, len(slots))
    assert (bias_corr is not None) == (kind == "adamw")
    tile = min(tile, P)
    assert tile % QTILE == 0, f"P={P} tile={tile}"
    assert blk.shape == (pl.cdiv(P, tile),), (blk.shape, P, tile)
    rows = _row_block(n, interpret)
    sp = _block_specs(rows, tile)

    in_specs = [sp.mask, sp.mask, sp.smem, sp.row, sp.row, sp.srow, sp.srow,
                sp.row, sp.srow, sp.srow, sp.vec, sp.vec] + [sp.vec] * n_slots
    args = [_mask_col(commit_mask), _mask_col(start_mask),
            blk.astype(jnp.int32), fresh.astype(jnp.float32),
            gw_q, gw_scale, gw_touched, in_q, in_scale, in_touched,
            g_bar, w] + list(slots)
    if kind == "adamw":
        in_specs.append(sp.sc2)
        args.append(bias_corr.astype(jnp.float32))

    kernel = functools.partial(_round_apply_sparse_kernel, n_workers=n,
                               kind=kind, hp=tuple(hp), topk=topk)
    out = _pallas_call(
        kernel, n=n, P=P, tile=tile, rows=rows, interpret=interpret,
        slab_itemsize=1, n_slots=n_slots, compressed=True, in_specs=in_specs,
        out_specs=[sp.row, sp.srow, sp.srow, sp.row, sp.srow, sp.srow,
                   sp.vec, sp.vec] + [sp.vec] * n_slots,
        out_shape=[
            jax.ShapeDtypeStruct((n, P), jnp.int8),
            jax.ShapeDtypeStruct((n, t), jnp.float32),
            jax.ShapeDtypeStruct((n, t), gw_touched.dtype),
            jax.ShapeDtypeStruct((n, P), jnp.int8),
            jax.ShapeDtypeStruct((n, t), jnp.float32),
            jax.ShapeDtypeStruct((n, t), in_touched.dtype),
            jax.ShapeDtypeStruct((P,), jnp.float32),
            jax.ShapeDtypeStruct((P,), w.dtype),
        ] + [jax.ShapeDtypeStruct((P,), jnp.float32)] * n_slots,
        first_alias=4,
    )(*args)
    return (out[0], out[1], out[2], out[3], out[4], out[5], out[6], out[7],
            tuple(out[8:]))


def dude_update_pallas(
    commit_mask: jnp.ndarray,   # [n] bool
    start_mask: jnp.ndarray,    # [n] bool
    fresh: jnp.ndarray,         # [n, P] fresh gradients (live model)
    g_workers: jnp.ndarray,     # [n, P] buffer dtype
    inflight: jnp.ndarray,      # [n, P] buffer dtype
    g_bar: jnp.ndarray,         # [P] f32
    w: jnp.ndarray,             # [P] f32 params
    *,
    eta: float,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
):
    """Historical fold-in-SGD entry point; the ``kind="sgd"`` case of
    ``dude_round_apply_pallas``.  Returns (g_workers', inflight', g_bar', w')."""
    gw, infl, gbar, w_new, _ = dude_round_apply_pallas(
        commit_mask, start_mask, fresh, g_workers, inflight, g_bar, w,
        kind="sgd", hp=(("lr", eta),), tile=tile, interpret=interpret,
    )
    return gw, infl, gbar, w_new
