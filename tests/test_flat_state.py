"""Flat-state training acceptance tests.

The FlatTrainState path keeps master params and optimizer slots in the
engine's segment-range ``[P]`` slab layout and fuses the DuDe round with the
optimizer apply (``DuDeEngine.round_apply``).  This file proves:

* flat-vs-pytree optimizer equivalence: N steps of the flat apply on raveled
  state match the pytree apply bit-for-bit after unravel, for
  sgd/momentum/adamw on all three engine backends (and sharded on the
  8-device mesh);
* the full flat train step matches the pytree train step bit-for-bit on
  params after 5 rounds;
* the sharded ``round_apply`` moves ZERO bytes (no collective ops in the
  compiled HLO), and the compiled flat train step contains exactly ONE
  params-shaped ``f32[P]`` all-gather — the single gather feeding the
  forward;
* optimizer slot shardings match the corresponding param shardings for all
  three optimizers (AdamW's ``m/``/``v/`` path prefixes must not skew the
  name-pattern rules);
* checkpoints: bf16 leaves round-trip through the uint16 npz encoding, the
  flat state round-trips with its spec manifest, and flat <-> legacy pytree
  checkpoints convert in both directions.

Multi-device tests follow the test_engine_sharded.py pattern: skipped below
8 devices and re-run by ``test_flat_sharded_suite_subprocess`` under
``--xla_force_host_platform_device_count=8``; CI also runs this file
in-process on the 8-device host mesh.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import NDEV, collective_counts, multidevice, p_mesh
from repro.core.engine import BACKENDS, DuDeEngine
from repro.core.flatten import make_flat_spec
from repro.launch.mesh import make_mesh
from repro.optim import adamw, flat_twin, momentum_sgd, sgd

OPTIMIZERS = {
    "sgd": lambda: sgd(0.05),
    "momentum": lambda: momentum_sgd(0.05, beta=0.9, nesterov=True),
    "adamw": lambda: adamw(0.01, weight_decay=0.1),
}


def _tree(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(13, 17)), jnp.float32),
        "emb": jnp.asarray(rng.normal(size=(4, 3, 9)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=5), jnp.float32),
    }


def _zpad(spec, x):
    return x.at[..., spec.size:].set(0)


def _small_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="flat-test-lm", arch_type="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
        dtype=jnp.float32, remat=False, attn_chunk=16, n_workers=4,
    )


# ------------------------------------ flat == pytree optimizer equivalence


@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_flat_apply_matches_pytree_apply(backend, opt_name):
    """round_apply (flat params + slots) == round + unravel + pytree apply,
    bit-for-bit over 6 steps, for every backend x optimizer."""
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    spec = make_flat_spec(tree)
    n, P = 5, spec.padded_size
    popt = OPTIMIZERS[opt_name]()
    fopt = flat_twin(popt)
    eng = DuDeEngine(spec=spec, n_workers=n, backend=backend, interpret=True)
    st = eng.init()._replace(
        g_workers=_zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32)),
        inflight=_zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32)))
    st2 = st
    w = spec.ravel(tree)
    params = tree
    ost = fopt.init(w)
    post = popt.init(params)

    @jax.jit
    def flat_step(st, f, a, b, w, ost):
        return eng.round_apply(st, f, a, b, w, ost, fopt)

    @jax.jit
    def tree_step(st, f, a, b, params, post):
        st, g = eng.round(st, f, a, b)
        params, post = popt.apply(params, spec.unravel(g), post)
        return st, g, params, post

    for t in range(6):
        fresh = _zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32))
        sm = jnp.asarray(rng.random(n) < 0.5)
        cm = jnp.asarray(rng.random(n) < 0.5)
        st, g, w, ost = flat_step(st, fresh, sm, cm, w, ost)
        st2, g2, params, post = tree_step(st2, fresh, sm, cm, params, post)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g2))
    back = spec.unravel(w)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]),
                                      err_msg=f"{opt_name}/{backend}/{k}")
    # pad lanes are a fixed point of every apply rule
    assert float(jnp.max(jnp.abs(w[spec.size:]))) == 0.0
    assert int(ost.step) == 6 == int(post.step)


@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_flat_train_step_matches_pytree(opt_name):
    """Acceptance: the flat train step and a hand-rolled PYTREE reference
    (vmapped backward -> engine.round -> unravel -> pytree opt.apply —
    exactly the retired tuple step's math) agree bit-for-bit on params
    after 5 train steps on a small LM config."""
    from repro.core import DuDeConfig
    from repro.launch.steps import (TrainOptions, init_flat_train_state,
                                    make_engine, make_train_step)
    from repro.models import lm_init, loss_fn

    cfg = _small_cfg()
    n = cfg.n_workers
    popt = OPTIMIZERS[opt_name]()
    dude_cfg = DuDeConfig(n, jnp.float32)
    options = TrainOptions()
    engine = make_engine(cfg, None, dude_cfg, options)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    opt_state = popt.init(params)
    dude_state = engine.init()
    fstate = init_flat_train_state(engine, popt, params)

    @jax.jit
    def pstep(params, opt_state, dude_state, batch, sm, cm):
        def per_worker(p, wb):
            (_, m), g = jax.value_and_grad(
                lambda q: loss_fn(q, wb, cfg), has_aux=True)(p)
            return g, m["loss"]

        grads, losses = jax.vmap(per_worker, in_axes=(None, 0))(params, batch)
        fresh = engine.spec.ravel_stacked(grads, jnp.float32)
        dude_state, g_flat = engine.round(dude_state, fresh, sm, cm)
        params, opt_state = popt.apply(params, engine.spec.unravel(g_flat),
                                       opt_state)
        return params, opt_state, dude_state, {"loss": jnp.mean(losses)}

    fstep = jax.jit(make_train_step(cfg, None, popt, dude_cfg, engine=engine,
                                    options=options))
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (n, 2, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, 2, 16), 0, cfg.vocab_size),
    }
    rng = np.random.default_rng(7)
    for r in range(5):
        sm = jnp.asarray(rng.random(n) < 0.6)
        cm = jnp.asarray(rng.random(n) < 0.6)
        params, opt_state, dude_state, m1 = pstep(
            params, opt_state, dude_state, batch, sm, cm)
        fstate, m2 = fstep(fstate, batch, sm, cm)
    back = engine.spec.unravel(fstate.params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m1["loss"]) == float(m2["loss"])


# ------------------------------------------------- slot sharding satellite


@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_slot_shardings_match_param_shardings(opt_name):
    """Every optimizer slot must shard exactly like its parameter — on the
    REAL model tree, whose ``groups`` stack lives at the root (so AdamW's
    ``m/``/``v/`` prefixes used to shift the path patterns).  Exercised
    directly on the sharding rules (the retired pytree train state was the
    original consumer; serving/params paths still use them)."""
    from repro.configs import get_config
    from repro.launch.steps import abstract_params
    from repro.sharding import param_shardings, slot_shardings

    cfg = get_config("qwen2_0_5b").smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = OPTIMIZERS[opt_name]()
    params = abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    p_sh = param_shardings(params, mesh)
    if not opt_state.slots:
        return
    slot_sh = slot_shardings(params, opt_state.slots, mesh)
    p_struct = jax.tree_util.tree_structure(p_sh)
    if jax.tree_util.tree_structure(slot_sh) == p_struct:
        subtrees = [slot_sh]                      # momentum: params-shaped
    else:
        assert isinstance(slot_sh, dict)          # adamw: {"m", "v"}
        subtrees = list(slot_sh.values())
    p_leaves = jax.tree.leaves(p_sh)
    for sub in subtrees:
        assert jax.tree_util.tree_structure(sub) == p_struct
        for s, p in zip(jax.tree.leaves(sub), p_leaves):
            assert s == p, (s, p)


# --------------------------------------------------------- checkpointing


def test_ckpt_bf16_roundtrip(tmp_path):
    """bf16 leaves survive the uint16 npz encoding: logical dtypes recorded
    once in the manifest, bit-exact values back."""
    from repro.checkpoint import (checkpoint_format, restore_checkpoint,
                                  save_checkpoint)
    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.normal(size=(7, 3)), jnp.bfloat16),
        "b": jnp.asarray(rng.normal(size=11), jnp.float32),
        "c": jnp.arange(5, dtype=jnp.int32),
    }
    save_checkpoint(str(tmp_path), 3, tree)
    assert checkpoint_format(str(tmp_path)) == "pytree"
    back = restore_checkpoint(str(tmp_path), 3, tree)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(
            np.asarray(back[k], np.float32), np.asarray(tree[k], np.float32))


def _flat_state(opt_name="adamw", buffer_dtype=jnp.bfloat16):
    from repro.launch.steps import init_flat_train_state
    rng = np.random.default_rng(1)
    tree = _tree(rng)
    spec = make_flat_spec(tree)
    eng = DuDeEngine(spec=spec, n_workers=3, buffer_dtype=buffer_dtype,
                     interpret=True)
    state = init_flat_train_state(eng, OPTIMIZERS[opt_name](), tree)
    # make the slabs non-trivial so the round-trip means something
    state = state._replace(engine=state.engine._replace(
        g_bar=_zpad(spec, jnp.asarray(rng.normal(size=spec.padded_size),
                                      jnp.float32))))
    return tree, spec, eng, state


def test_ckpt_flat_state_roundtrip(tmp_path):
    """FlatTrainState (incl. bf16 engine slabs) saves with the spec segment
    table in the manifest and restores bit-exactly."""
    from repro.checkpoint import (checkpoint_format, restore_checkpoint,
                                  save_checkpoint)
    _, spec, _, state = _flat_state()
    save_checkpoint(str(tmp_path), 5, state, flat_spec=spec)
    assert checkpoint_format(str(tmp_path)) == "flat"
    back = restore_checkpoint(str(tmp_path), 5, state, flat_spec=spec)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_ckpt_flat_pytree_interop(tmp_path):
    """Legacy pytree checkpoints load into flat runs and vice versa."""
    from repro.checkpoint import (restore_flat_from_pytree,
                                  restore_params_from_flat, save_checkpoint)
    tree, spec, _, state = _flat_state(opt_name="sgd")

    # flat checkpoint -> pytree params
    save_checkpoint(str(tmp_path / "flat"), 1, state, flat_spec=spec)
    params = restore_params_from_flat(str(tmp_path / "flat"), 1, tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(tree[k]))

    # legacy pytree checkpoint -> flat state (params slab overwritten)
    tree2 = jax.tree.map(lambda x: x + 1, tree)
    save_checkpoint(str(tmp_path / "tree"), 2, tree2)
    st2 = restore_flat_from_pytree(str(tmp_path / "tree"), 2, state, spec)
    np.testing.assert_array_equal(np.asarray(st2.params),
                                  np.asarray(spec.ravel(tree2)))
    # non-params slabs untouched
    np.testing.assert_array_equal(np.asarray(st2.engine.g_bar),
                                  np.asarray(state.engine.g_bar))


def test_ckpt_flat_refit_mesh_axis_size(tmp_path):
    """A flat checkpoint saved unsharded restores under a shard-aligned spec
    (bigger pad tail): the real prefix is preserved, pads stay zero."""
    from repro.checkpoint import restore_params_from_flat, save_checkpoint
    tree, spec, _, state = _flat_state(opt_name="sgd")
    save_checkpoint(str(tmp_path), 1, state, flat_spec=spec)
    spec8 = make_flat_spec(tree, mesh_axis_size=8)
    assert spec8.padded_size > spec.padded_size
    params = restore_params_from_flat(str(tmp_path), 1, tree)
    np.testing.assert_array_equal(np.asarray(spec8.ravel(params)[:spec.size]),
                                  np.asarray(state.params[:spec.size]))


# ------------------------------------------------------- sharded (8-dev)


@multidevice
@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_round_apply_sharded_matches_unsharded(backend, opt_name):
    """P-axis sharded round_apply == single-device round_apply, bit-for-bit
    on params, slots, and g_bar."""
    from repro.sharding import flat_train_state_shardings

    rng = np.random.default_rng(3)
    tree = _tree(rng)
    spec = make_flat_spec(tree, mesh_axis_size=NDEV)
    n, P = 4, spec.padded_size
    mesh = p_mesh()
    popt = OPTIMIZERS[opt_name]()
    fopt = flat_twin(popt)
    kw = dict(spec=spec, n_workers=n, backend=backend, interpret=True)
    eng_u = DuDeEngine(**kw)
    eng_s = DuDeEngine(**kw, mesh=mesh, axis_name="p")
    su = eng_u.init()._replace(
        g_workers=_zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32)),
        inflight=_zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32)))
    w = spec.ravel(tree)
    ost = fopt.init(w)
    sh = flat_train_state_shardings(spec, mesh, ("p",), ost)
    ss = jax.device_put(su, eng_s.shardings())
    ws = jax.device_put(w, sh.params)
    osts = jax.device_put(ost, sh.opt)
    fu = jax.jit(lambda s, f, a, b, w, o: eng_u.round_apply(s, f, a, b, w, o, fopt))
    fs = jax.jit(lambda s, f, a, b, w, o: eng_s.round_apply(s, f, a, b, w, o, fopt))
    for t in range(4):
        fresh = _zpad(spec, jnp.asarray(rng.normal(size=(n, P)), jnp.float32))
        sm = jnp.asarray(rng.random(n) < 0.5)
        cm = jnp.asarray(rng.random(n) < 0.5)
        su, gu, w, ost = fu(su, fresh, sm, cm, w, ost)
        ss, gs, ws, osts = fs(ss, fresh, sm, cm, ws, osts)
    np.testing.assert_array_equal(np.asarray(gu), np.asarray(gs))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ws))
    for a, b in zip(jax.tree.leaves(ost), jax.tree.leaves(osts)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multidevice
@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_round_apply_moves_no_bytes(backend):
    """Round + slot update + param step are all elementwise on P: the
    compiled sharded round_apply must contain ZERO collective ops — this is
    the 'no all-gather/all-reduce between the engine round and the param
    update' acceptance criterion, enforced structurally (one shard_map)."""
    rng = np.random.default_rng(5)
    spec = make_flat_spec(_tree(rng), mesh_axis_size=NDEV)
    n = 4
    mesh = p_mesh()
    fopt = flat_twin(OPTIMIZERS["adamw"]())
    eng = DuDeEngine(spec=spec, n_workers=n, backend=backend,
                     interpret=True, mesh=mesh, axis_name="p")
    state = eng.init()
    w = jax.device_put(jnp.zeros(eng.P), eng.shardings().g_bar)
    ost = fopt.init(w)
    fresh = jax.device_put(jnp.ones((n, eng.P)), eng.shardings().g_workers)
    ones = jnp.ones(n, bool)
    hlo = jax.jit(lambda s, f, a, b, w, o: eng.round_apply(s, f, a, b, w, o, fopt)
                  ).lower(state, fresh, ones, ones, w, ost
                          ).compile().as_text()
    counts = {k: v for k, v in collective_counts(hlo).items() if v}
    assert not counts, counts


@multidevice
def test_flat_train_step_single_params_allgather():
    """The compiled flat train step on a 2x4 data x model mesh contains
    exactly ONE params-shaped f32[P] all-gather — the single gather feeding
    the forward — and runs finite."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core.dude import DuDeConfig
    from repro.launch.steps import (TrainOptions, abstract_train_state,
                                    init_flat_train_state, make_engine,
                                    make_train_step)
    from repro.models import lm_init

    cfg = get_config("qwen2_0_5b").smoke()
    mesh = make_mesh((2, 4), ("data", "model"))
    n = cfg.n_workers
    dude_cfg = DuDeConfig(n, jnp.float32)
    opt = momentum_sgd(0.05)
    options = TrainOptions()
    with mesh:
        engine = make_engine(cfg, mesh, dude_cfg, options)
        st_shapes, st_sh = abstract_train_state(cfg, mesh, opt, dude_cfg,
                                                options=options)
        step = jax.jit(make_train_step(cfg, mesh, opt, dude_cfg,
                                       options=options, engine=engine))
        key = jax.random.PRNGKey(1)
        b_sh = NamedSharding(mesh, P(None, "data", None))
        batch = {
            "tokens": jax.device_put(
                jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size), b_sh),
            "labels": jax.device_put(
                jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size), b_sh),
        }
        ones = jnp.ones(n, bool)
        hlo = step.lower(st_shapes, batch, ones, ones).compile().as_text()
        # exactly one all-gather producing the full [P] master vector
        agp = re.findall(rf"f32\[{engine.P}\]\S* all-gather\(", hlo)
        assert len(agp) == 1, (engine.P, len(agp))
        state = init_flat_train_state(engine, opt,
                                      lm_init(jax.random.PRNGKey(0), cfg))
        for _ in range(2):
            state, metrics = step(state, batch, ones, ones)
        assert np.isfinite(float(metrics["loss"]))


# ------------------------------------------------------ subprocess driver


def test_flat_sharded_suite_subprocess():
    """Run the in-process multidevice tests above on 8 host-platform devices
    (they are skipped in a default single-device session)."""
    if jax.device_count() >= NDEV:
        pytest.skip("already multi-device in-process")
    repo = Path(__file__).resolve().parent.parent
    env = {
        **os.environ,
        "PYTHONPATH": "src",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                      + f" --xla_force_host_platform_device_count={NDEV}"
                      ).strip(),
    }
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(Path(__file__).resolve()),
         # only the tests the single-device skip guard deferred
         "-k", "(sharded or allgather) and not subprocess"],
        capture_output=True, text=True, timeout=540, env=env, cwd=repo,
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "skipped" not in r.stdout.splitlines()[-1], r.stdout[-500:]
