"""The MoE layer (``models/moe.py``) and the OLMoE model path, on the CPU at
small sizes:

* the expert share: the outputs of the 8 shares of a 64-expert layer add up
  to the uncut layer;
* drop-free dispatch: under a router that sends every token to one expert,
  the layer equals a per-token loop and drops nothing; with fewer experts
  held than picked and every held expert in every token's top-k, output
  and per-worker gradients equal the dispatch-free sum;
* ``norm_topk_prob`` false leaves the top-k gates as the softmax gave them;
* the full-width q/k norm equals its formula;
* the aux loss equals Hugging Face's ``load_balancing_loss_func`` over the
  concatenated router logits of every layer;
* the round step's MoE scopes nest inside ``dude.backward``;
* the program's loss and per-leaf gradients match the benchmark's plain
  reference (``benchmarks/chip/configs/olmoe.py``) on seeded weights.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm_init, loss_fn
from repro.models.attention import AttnConfig, attention_init, _project_qkv
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.models.moe import (
    MoEConfig, load_balancing_loss, moe_apply, moe_init, moe_stats_zero,
)
from repro.runtime import spans

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
E, K, D, F = 64, 8, 32, 16


def _share(p, off, held):
    return dict(p, **{n: p[n][off:off + held]
                      for n in ("wgate", "wup", "wdown")})


def _x(shape, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_eight_shares_sum_to_the_uncut_layer(norm_topk_prob):
    full = MoEConfig(D, E, K, F, norm_topk_prob=norm_topk_prob)
    p = moe_init(jax.random.PRNGKey(0), full)
    x = _x((2, 12, D))
    with jax.default_matmul_precision("highest"):
        whole, st = moe_apply(p, x, full)
        parts = []
        for off in range(0, E, 8):
            cfg = dataclasses.replace(full, experts_held=8, expert_offset=off)
            y, s = moe_apply(_share(p, off, 8), x, cfg)
            parts.append(y)
            # every share sees the whole router
            np.testing.assert_array_equal(s["count"], st["count"])
    held = [float(moe_apply(_share(p, off, 8), x, dataclasses.replace(
        full, experts_held=8, expert_offset=off))[1]["rows_held"])
        for off in range(0, E, 8)]
    assert sum(held) == 2 * 12 * K
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)


def test_drop_free_under_one_hot_router():
    """Every token picks expert 0 first (a router that sends all tokens to
    one expert): the layer equals a loop over tokens, none dropped."""
    cfg = MoEConfig(D, 16, 4, F, experts_held=4, expert_offset=0,
                    norm_topk_prob=False)
    p = moe_init(jax.random.PRNGKey(3), cfg)
    p["router"]["kernel"] = p["router"]["kernel"].at[:, 0].set(0.0)
    x = _x((1, 24, D)).at[..., 0].set(0.0)
    x = x.at[..., 1].set(10.0)
    p["router"]["kernel"] = p["router"]["kernel"].at[1, 0].set(5.0)
    with jax.default_matmul_precision("highest"):
        y, st = moe_apply(p, x, cfg)
        xf = x.reshape(-1, D)
        probs = jax.nn.softmax(xf @ p["router"]["kernel"], -1)
        want = []
        for t in range(xf.shape[0]):
            g, ids = jax.lax.top_k(probs[t], 4)
            assert int(ids[0]) == 0
            row = jnp.zeros((D,))
            for gj, e in zip(g, ids):
                if int(e) < 4:
                    h = jax.nn.silu(xf[t] @ p["wgate"][e]) \
                        * (xf[t] @ p["wup"][e])
                    row = row + gj * (h @ p["wdown"][e])
            want.append(row)
    np.testing.assert_allclose(y.reshape(-1, D), jnp.stack(want),
                               rtol=2e-5, atol=2e-6)
    assert float(st["rows_dropped"]) == 0.0
    assert float(st["rows_held"]) >= 24


def _dense_share(p, x, cfg):
    """The held experts' output as a sum over experts of every token's gate
    times the expert's SwiGLU: the layer with no dispatch at all."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xf @ p["router"]["kernel"], -1)
    g, ids = jax.lax.top_k(probs, cfg.experts_per_tok)
    if cfg.norm_topk_prob:
        g = g / g.sum(-1, keepdims=True)
    y = jnp.zeros_like(xf)
    for e in range(cfg.held):
        w = jnp.sum(jnp.where(ids == cfg.expert_offset + e, g, 0.0), -1)
        h = jax.nn.silu(xf @ p["wgate"][e]) * (xf @ p["wup"][e])
        y = y + w[:, None] * (h @ p["wdown"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("held,k", [(1, 2), (2, 4), (3, 4)])
def test_held_below_k_with_every_token_on_held_experts(held, k):
    """Fewer experts held than picked per token, and the router forced to
    put every held expert in every token's top-k: the buffer is full of held
    pairs and the other pairs sort past it.  Output and gradients (per
    worker, under the round step's vmap) equal the dispatch-free sum."""
    cfg = MoEConfig(D, 8, k, F, experts_held=held, expert_offset=0,
                    norm_topk_prob=False)
    p = moe_init(jax.random.PRNGKey(8), cfg)
    p["router"]["kernel"] = p["router"]["kernel"].at[1, :held].set(
        5.0 - 0.5 * jnp.arange(held))
    x = _x((2, 1, 12, D), 9).at[..., 1].set(10.0)     # [workers, B, S, d]
    with jax.default_matmul_precision("highest"):
        _, ids = jax.lax.top_k(jax.nn.softmax(
            x.reshape(-1, D) @ p["router"]["kernel"], -1), k)
        assert bool(jnp.all(jnp.sort(ids, -1)[:, :held]
                            == jnp.arange(held)))
        y, st = moe_apply(p, x[0], cfg)
        np.testing.assert_allclose(y, _dense_share(p, x[0], cfg),
                                   rtol=2e-5, atol=2e-6)
        assert float(st["rows_held"]) == 12 * held
        assert float(st["rows_dropped"]) == 0.0

        def grads(fn):
            loss = lambda q, z: jnp.sum(fn(q, z) ** 2)  # noqa: E731
            return jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1)),
                                    in_axes=(None, 0)))(p, x)
        got = grads(lambda q, z: moe_apply(q, z, cfg)[0])
        want = grads(lambda q, z: _dense_share(q, z, cfg))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_norm_topk_prob_false_keeps_the_softmax_gates():
    """With one expert held and top-k gates unnormalised, a token's output
    is its softmax probability of that expert times the expert."""
    for norm in (False, True):
        cfg = MoEConfig(D, 8, 2, F, experts_held=1, expert_offset=3,
                        norm_topk_prob=norm)
        p = moe_init(jax.random.PRNGKey(5), cfg)
        x = _x((1, 16, D), 6)
        with jax.default_matmul_precision("highest"):
            y, _ = moe_apply(p, x, cfg)
            xf = x.reshape(-1, D)
            probs = jax.nn.softmax(xf @ p["router"]["kernel"], -1)
            g, ids = jax.lax.top_k(probs, 2)
            if norm:
                g = g / g.sum(-1, keepdims=True)
            gate = jnp.sum(jnp.where(ids == 3, g, 0.0), -1)
            h = jax.nn.silu(xf @ p["wgate"][0]) * (xf @ p["wup"][0])
            want = gate[:, None] * (h @ p["wdown"][0])
        np.testing.assert_allclose(y.reshape(-1, D), want, rtol=2e-5,
                                   atol=2e-6)
        assert bool(jnp.any(gate > 0))


def test_full_width_qk_norm_equals_its_formula():
    cfg = AttnConfig(d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
                     qk_norm="full")
    p = attention_init(jax.random.PRNGKey(0), cfg)
    assert p["q_norm"]["scale"].shape == (32,)
    assert p["k_norm"]["scale"].shape == (16,)
    p["q_norm"]["scale"] = 1.0 + _x((32,), 2)
    p["k_norm"]["scale"] = 1.0 + _x((16,), 3)
    x = _x((1, 5, 32), 4)
    pos = jnp.arange(5)[None]
    with jax.default_matmul_precision("highest"):
        q, k, _ = _project_qkv(p, x, pos, cfg, lambda a, n: a)

        def norm(y, s):
            return y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5) * s

        q_want = norm(x @ p["wq"]["kernel"], p["q_norm"]["scale"])
        k_want = norm(x @ p["wk"]["kernel"], p["k_norm"]["scale"])
    np.testing.assert_allclose(
        q, apply_rope(q_want.reshape(1, 5, 4, 8), pos), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        k, apply_rope(k_want.reshape(1, 5, 2, 8), pos), rtol=1e-5, atol=1e-6)


def _hf_load_balancing_loss(gate_logits, num_experts, top_k):
    """Hugging Face's ``load_balancing_loss_func`` (no attention mask), in
    numpy: the router logits of every layer concatenated."""
    logits = np.concatenate(gate_logits, axis=0)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    sel = np.argsort(-w, axis=-1, kind="stable")[:, :top_k]
    mask = np.eye(num_experts)[sel]                  # [N, k, E]
    tokens_per_expert = mask.mean(axis=0)            # [k, E]
    router_prob_per_expert = w.mean(axis=0)          # [E]
    return num_experts * np.sum(tokens_per_expert
                                * router_prob_per_expert[None])


def test_aux_loss_is_the_hf_definition():
    cfg = MoEConfig(D, 16, 4, F, experts_held=4, expert_offset=8)
    layers = [moe_init(jax.random.PRNGKey(i), cfg) for i in range(3)]
    x = _x((2, 10, D), 7)
    stats = moe_stats_zero(16)
    logits = []
    with jax.default_matmul_precision("highest"):
        for p in layers:
            _, s = moe_apply(p, x, cfg)
            stats = jax.tree.map(jnp.add, stats, s)
            logits.append(np.asarray(x.reshape(-1, D) @ p["router"]["kernel"]))
    got = float(load_balancing_loss(stats, 16))
    want = _hf_load_balancing_loss(logits, 16, 4)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def _olmoe_small():
    """The benchmark's OLMoE file at the test-suite size (as
    ``benchmarks/chip/tests/test_faults.py`` cuts it)."""
    sys.path.insert(0, str(BENCH))
    import harness
    cfg = json.loads((BENCH / "configs" / "olmoe-1b-7b.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=32, vocab_size=96,
               num_hidden_layers=2, expert_offset=8)
    cfg["run"] = dict(cfg["run"], ce_chunk=8)
    ref, prog = harness.load_family(cfg)
    mc = dataclasses.replace(prog.model_config(cfg, 1), dtype=jnp.float32,
                             remat=True)
    return cfg, ref, prog, mc


def test_program_matches_the_plain_reference():
    cfg, ref, prog, mc = _olmoe_small()
    w = ref.init(jax.random.PRNGKey(11), cfg)
    S = 24
    toks = jax.random.randint(jax.random.PRNGKey(12), (S + 1,), 0,
                              cfg["vocab_size"])
    batch = {"tokens": toks[None, :-1], "labels": toks[None, 1:]}
    params = prog.to_program(w)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), mc)))
    with jax.default_matmul_precision("highest"):
        (total, m), g = jax.value_and_grad(
            lambda p: loss_fn(p, batch, mc), has_aux=True)(params)
        ce, aux = ref.parts(w, batch["tokens"][0], batch["labels"][0], cfg)
        g_ref = prog.to_program(jax.grad(lambda q: ref.loss(
            q, batch["tokens"][0], batch["labels"][0], cfg))(w))
    np.testing.assert_allclose(m["loss"], ce, rtol=1e-5)
    np.testing.assert_allclose(m["aux"], aux, rtol=1e-5)
    assert float(aux) > 0
    assert float(m["moe_rows_dropped"]) == 0.0
    assert 0 < float(m["moe_rows_held"]) <= 2 * S * 8
    leaves = jax.tree_util.tree_leaves_with_path(g)
    ref_leaves = jax.tree.leaves(g_ref)
    assert len(leaves) == len(ref_leaves)
    for (path, a), b in zip(leaves, ref_leaves):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_round_step_moe_scopes_nest_in_backward():
    """In the round step lowered for the TPU every computing op of the MoE
    layer sits under ``dude.backward`` and one MoE scope; the grouped
    matmuls under ``dude.moe_experts``, the sort under ``dude.moe_route``."""
    from repro.api import Trainer, TrainerConfig
    from test_spans import _computing, scoped_ops
    _, _, _, mc = _olmoe_small()
    mc = dataclasses.replace(mc, n_workers=2)
    t = Trainer.create(TrainerConfig(arch=mc, lr=0.05, seed=1))
    toks = jnp.zeros((2, 1, 16), jnp.int32)
    m = jnp.ones((2,), bool)
    lowered = jax.jit(t.step_fn).trace(
        t.state, {"tokens": toks, "labels": toks}, m, m).lower(
        lowering_platforms=("tpu",))
    moe = {spans.MOE_ROUTE, spans.MOE_EXPERTS}
    # index tables (iotas) are constants, traced where the vmap rule runs
    ops = [(n, sc) for n, sc in _computing(scoped_ops(lowered))
           if sc & moe and n != "stablehlo.iota"]
    assert ops
    for n, sc in ops:
        assert spans.BACKWARD in sc and len(sc & moe) == 1, (n, sc)
    kinds = {(n, s) for n, sc in ops for s in sc & moe}
    assert ("stablehlo.ragged_dot", spans.MOE_EXPERTS) in kinds \
        or any(n.endswith("ragged_dot") for n, _ in kinds), kinds
    assert ("stablehlo.sort", spans.MOE_ROUTE) in kinds, kinds
