"""The vocabulary-sliced cross-entropy of ``loss_fn`` (``ce_chunk > 0``)
against the unchunked loss, and the shape of its compiled backward."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.hlo_analysis import _INSTR_RE, _split_computations
from repro.models import lm_init, loss_fn

C = 8
# per-leaf gradient norm gap to the unchunked loss.  In float32 the slices
# only reorder sums.  In bfloat16 each slice's share of the hidden state's
# cotangent is rounded apart, and the layers below carry that rounding into
# every leaf; both losses sit about 2 % from a float32 reference here.
GRAD_TOL = {"bf16": 2.0 ** -5, "f32": 1e-5}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def _cfg(tied: bool, vocab: int, ce_chunk: int, dtype=jnp.bfloat16):
    return dataclasses.replace(
        get_config("qwen2_0_5b").smoke(), num_layers=1, d_model=64,
        num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128, vocab_size=vocab,
        tie_embeddings=tied, dtype=dtype, ce_chunk=ce_chunk)


def _batch(cfg, B: int, S: int):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    tokens = jax.random.randint(k1, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    drop = jax.random.bernoulli(k2, 0.2, (B, S)).at[:, -1].set(True)
    return {"tokens": tokens, "labels": jnp.where(drop, -1, labels)}


def _loss_and_grads(params, batch, cfg):
    f = lambda p: loss_fn(p, batch, cfg)[0]  # noqa: E731
    return jax.jit(jax.value_and_grad(f))(params)


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
@pytest.mark.parametrize("vocab", [96, 97], ids=["v_even", "v_padded"])
@pytest.mark.parametrize("S", [4 * C, 4 * C - 3], ids=["s_whole", "s_ragged"])
@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_sliced_ce_matches_unchunked(tied, B, S, vocab, dtype):
    """Four slices (``ceil(S / C)``) of 24 rows, or of 25 with 3 padded,
    give the unchunked loss and gradients, with -1 labels in the batch."""
    cfg = _cfg(tied, vocab, C, DTYPES[dtype])
    params = lm_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, B, S)
    loss, grads = _loss_and_grads(params, batch, cfg)
    ref_loss, ref_grads = _loss_and_grads(
        params, batch, dataclasses.replace(cfg, ce_chunk=0))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    head = "['embed']['embedding']" if tied else "['head']['kernel']"
    seen = set()
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        assert np.isfinite(g).all(), name
        gap = np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30)
        assert gap <= GRAD_TOL[dtype], (name, gap)
        seen.add(name)
    assert head in seen


def test_sliced_ce_slices_of_padding_alone():
    """10 rows over ``ceil(64 / 8) = 8`` slices of 2: the last three are all
    padding, their logsumexp -inf, and loss and gradients stay finite."""
    cfg = _cfg(True, 10, C, jnp.float32)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, 1, 8 * C)
    loss, grads = _loss_and_grads(params, batch, cfg)
    ref_loss, ref_grads = _loss_and_grads(
        params, batch, dataclasses.replace(cfg, ce_chunk=0))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    g, r = grads["embed"]["embedding"], ref_grads["embed"]["embedding"]
    assert np.isfinite(g).all()
    assert np.linalg.norm(g - r) <= GRAD_TOL["f32"] * np.linalg.norm(r)


def _while_shapes(hlo: str) -> list[str]:
    """The result shapes of every ``while`` op in a compiled module."""
    out = []
    for lines in _split_computations(hlo).values():
        for line in lines:
            m = _INSTR_RE.search(line)
            if m and m.group(2) == "while":
                out.append(m.group(1))
    return out


def test_sliced_ce_backward_has_no_head_sized_carry():
    """No loop of the compiled gradient carries the head's ``[V, d]``: each
    slice of its gradient leaves the scan as a ``ys`` slice, written once."""
    cfg = _cfg(True, 3072, 32)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, 1, 256)
    f = lambda p: loss_fn(p, batch, cfg)[0]  # noqa: E731
    hlo = jax.jit(jax.grad(f)).lower(params).compile().as_text()
    shapes = _while_shapes(hlo)
    assert shapes, "the sliced loss compiles to no loop"
    head = re.compile(r"\[%d,%d\]" % (cfg.vocab_size, cfg.d_model))
    assert not [s for s in shapes if head.search(s)], shapes
