"""The fused round kernels and the training attention kernels compile for
a described TPU v5e chip.

Nothing runs: each test lowers one kernel at shapes only, with the tile the
engine derives (``DuDeEngine.tile`` off interpret mode), compiles it with
the TPU compiler that ships with JAX, and checks the program holds the
Mosaic kernel (``tpu_custom_call``).  This catches what interpret mode
cannot: block layouts Mosaic refuses and steps that overrun VMEM.  The
topology is described inside a fixture, so collection never loads the TPU
library; where it cannot be described the tests skip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import DuDeEngine
from repro.core.flatten import make_flat_spec
from repro.kernels.dude_update import (
    SLOT_STREAMS, dude_round_apply_pallas, dude_round_apply_q_pallas,
    dude_round_apply_sparse_pallas,
)
from repro.kernels.splash_attention import causal_attention

# a multiple of the 128-lane pad that no derived tile divides: the last
# block of every grid is ragged
P_RAW = (1 << 22) + 5 * 128
HP = {"sgd": (("lr", 0.1),),
      "adamw": (("lr", 0.1), ("b1", 0.9), ("b2", 0.999), ("eps", 1e-8),
                ("weight_decay", 0.01))}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _engine(n, buffer_dtype=jnp.float32, commit_format="f32",
            sparse=False) -> DuDeEngine:
    spec = make_flat_spec({"w": jax.ShapeDtypeStruct((P_RAW,), jnp.float32)})
    return DuDeEngine(spec=spec, n_workers=n, buffer_dtype=buffer_dtype,
                      backend="pallas", interpret=False,
                      commit_format=commit_format, sparse_meta=sparse)


def _compile(fn, args, sharding):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in args]
    return jax.jit(fn).lower(*args).compile().as_text()


def _opt_args(kind, P):
    sds = jax.ShapeDtypeStruct
    slots = [sds((P,), jnp.float32)] * SLOT_STREAMS[kind]
    bc = [sds((2,), jnp.float32)] if kind == "adamw" else []
    return slots, bc


def _split(rest, kind):
    ns = SLOT_STREAMS[kind]
    return tuple(rest[:ns]), (rest[ns] if kind == "adamw" else None)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [4, 16, 64])
@pytest.mark.parametrize("kind", ["sgd", "adamw"])
def test_round_apply_compiles_for_v5e(one_chip, kind, n, dtype):
    """Dense slabs in ``dtype``; f32 fresh gradients."""
    eng = _engine(n, buffer_dtype=dtype)
    P, sds = eng.P, jax.ShapeDtypeStruct
    slots, bc = _opt_args(kind, P)

    def f(cm, sm, fresh, gw, infl, gbar, w, *rest):
        sl, b = _split(rest, kind)
        return dude_round_apply_pallas(cm, sm, fresh, gw, infl, gbar, w, sl,
                                       b, kind=kind, hp=HP[kind],
                                       tile=eng.tile)

    args = [sds((n,), jnp.bool_)] * 2 + [
        sds((n, P), jnp.float32), sds((n, P), dtype), sds((n, P), dtype),
        sds((P,), jnp.float32), sds((P,), jnp.float32)] + slots + bc
    assert "tpu_custom_call" in _compile(f, args, one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [4, 16, 64])
def test_round_apply_q_int8_compiles_for_v5e(one_chip, n, dtype):
    """int8_ef slabs; fresh gradients in ``dtype``; AdamW tail."""
    eng = _engine(n, commit_format="int8_ef")
    P, t, sds = eng.P, eng.n_tiles, jax.ShapeDtypeStruct
    slots, bc = _opt_args("adamw", P)

    def f(cm, sm, fresh, gq, gs, iq, is_, gbar, w, *rest):
        sl, b = _split(rest, "adamw")
        return dude_round_apply_q_pallas(cm, sm, fresh, gq, gs, iq, is_,
                                         gbar, w, sl, b, kind="adamw",
                                         hp=HP["adamw"], fmt="int8_ef",
                                         tile=eng.tile)

    args = [sds((n,), jnp.bool_)] * 2 + [
        sds((n, P), dtype), sds((n, P), jnp.int8), sds((n, t), jnp.float32),
        sds((n, P), jnp.int8), sds((n, t), jnp.float32),
        sds((P,), jnp.float32), sds((P,), jnp.float32)] + slots + bc
    assert "tpu_custom_call" in _compile(f, args, one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [4, 16, 64])
def test_round_apply_sparse_compiles_for_v5e(one_chip, n, dtype):
    """topk_ef slabs with touched-tile bitmaps; fresh gradients in
    ``dtype``; SGD tail."""
    eng = _engine(n, commit_format="topk_ef", sparse=True)
    P, t, sds = eng.P, eng.n_tiles, jax.ShapeDtypeStruct
    nb = -(-P // eng.tile)

    def f(cm, sm, blk, fresh, gq, gs, gt, iq, is_, it, gbar, w):
        return dude_round_apply_sparse_pallas(
            cm, sm, blk, fresh, gq, gs, gt, iq, is_, it, gbar, w,
            kind="sgd", hp=HP["sgd"], tile=eng.tile)

    args = [sds((n,), jnp.bool_)] * 2 + [
        sds((nb,), jnp.int32), sds((n, P), dtype),
        sds((n, P), jnp.int8), sds((n, t), jnp.float32),
        sds((n, t), jnp.int8), sds((n, P), jnp.int8),
        sds((n, t), jnp.float32), sds((n, t), jnp.int8),
        sds((P,), jnp.float32), sds((P,), jnp.float32)]
    assert "tpu_custom_call" in _compile(f, args, one_chip)


@pytest.mark.parametrize("n,seq,heads,kv_heads,hd", [
    (4, 4096, 16, 16, 128),     # olmoe-1b-7b.round.n4.s4096
    (8, 1024, 14, 2, 64),       # qwen2-0.5b.round.n8
    (2, 1024, 16, 8, 128),      # qwen3-1.7b.round.n2
])
def test_causal_attention_compiles_for_v5e(one_chip, n, seq, heads,
                                           kv_heads, hd):
    """The training attention's forward and backward kernels, vmapped over
    n workers of one sequence each, at the chip cells' widths."""
    sds = jax.ShapeDtypeStruct

    def f(q, k, v, cot):
        def loss(q, k, v):
            out = jax.vmap(causal_attention)(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * cot)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    args = [sds((n, 1, seq, heads, hd), jnp.bfloat16),
            sds((n, 1, seq, kv_heads, hd), jnp.bfloat16),
            sds((n, 1, seq, kv_heads, hd), jnp.bfloat16),
            sds((n, 1, seq, heads, hd), jnp.float32)]
    text = _compile(f, args, one_chip)
    # the forward, dkv and dq kernels
    assert text.count("tpu_custom_call") >= 3
