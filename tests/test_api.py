"""Session-API acceptance tests: one Trainer, one train state, one step
signature; config validation in one place; auto-format checkpoints; the
round-algo registry shared between the production step and the simulator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    CheckpointPolicy, ConfigError, ServeConfig, ServeSession, Trainer,
    TrainerConfig,
)
from repro.core import ROUND_ALGOS, make_algo, make_round_algo
from repro.core.engine import DuDeEngine
from repro.core.flatten import make_flat_spec
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.optim import sgd


def _tiny_cfg(n_workers=4):
    return ModelConfig(
        name="api-test-lm", arch_type="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=32,
        dtype=jnp.float32, remat=False, attn_chunk=16, n_workers=n_workers,
    )


def _batch(cfg, key=0, b=1, s=16):
    n = cfg.n_workers
    k = jax.random.PRNGKey(key)
    return {
        "tokens": jax.random.randint(k, (n, b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(k, (n, b, s), 0, cfg.vocab_size),
    }


def _tree(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(7, 5)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=11), jnp.float32),
    }


# ----------------------------------------------------- config validation


def test_config_dude_accum_requires_reference_backend():
    """The rule that used to live in argparse: typed error, not ap.error."""
    for backend in ("indexed", "pallas"):
        with pytest.raises(ConfigError, match="dude_accum.*reference"):
            TrainerConfig(arch=_tiny_cfg(), algo="dude_accum",
                          server_backend=backend)
    # reference is fine
    TrainerConfig(arch=_tiny_cfg(), algo="dude_accum",
                  server_backend="reference")
    # and ConfigError is a ValueError, so broad catches still work
    assert issubclass(ConfigError, ValueError)


def test_config_validates_names():
    with pytest.raises(ConfigError, match="unknown algo"):
        TrainerConfig(arch=_tiny_cfg(), algo="sgd_async")
    with pytest.raises(ConfigError, match="unknown server_backend"):
        TrainerConfig(arch=_tiny_cfg(), server_backend="fused")
    with pytest.raises(ConfigError, match="unknown optimizer"):
        TrainerConfig(arch=_tiny_cfg(), optimizer="lion")
    with pytest.raises(ConfigError, match="unknown arch"):
        TrainerConfig(arch="not-a-real-arch")
    with pytest.raises(ConfigError, match="directory"):
        CheckpointPolicy(every=5)


def test_config_accepts_arch_aliases():
    """Every spelling get_config resolves (registry ids AND dashed aliases
    like "qwen2-0.5b") must pass config validation — the drivers fed
    aliases straight to get_config before the session API existed."""
    for name in ("qwen2_0_5b", "qwen2-0.5b"):
        cfg = TrainerConfig(arch=name, smoke=True)
        assert cfg.model_config.name == "qwen2-0.5b"
        ServeConfig(arch=name, smoke=True, max_len=32)


def test_flat_optimizer_shims_removed():
    """PR-4's one-release deprecation window is over: the flat_optimizer=
    keyword and the TrainOptions field are GONE (the flat step is the only
    step), and the default make_train_step builds the flat signature."""
    import dataclasses
    from repro.launch.steps import (
        TrainOptions, init_flat_train_state, make_engine, make_train_step)
    from repro.models import lm_init
    cfg = _tiny_cfg()
    with pytest.raises(TypeError):
        make_train_step(cfg, None, flat_optimizer=True)
    assert "flat_optimizer" not in {
        f.name for f in dataclasses.fields(TrainOptions)}
    # the default step IS the flat one
    engine = make_engine(cfg)
    step = make_train_step(cfg, None, engine=engine)
    state = init_flat_train_state(engine, sgd(0.05),
                                  lm_init(jax.random.PRNGKey(0), cfg))
    ones = jnp.ones(cfg.n_workers, bool)
    state, metrics = jax.jit(step)(state, _batch(cfg), ones, ones)
    assert np.isfinite(float(metrics["loss"]))


# ------------------------------------------- one step signature, all algos


@pytest.mark.parametrize("algo", list(ROUND_ALGOS))
def test_trainer_single_signature_every_algo(algo):
    """Every registry rule — DuDe family AND round baselines — runs through
    the identical ``trainer.step(batch, sm, cm) -> metrics`` call over the
    single FlatTrainState."""
    cfg = _tiny_cfg()
    t = Trainer.create(TrainerConfig(arch=cfg, algo=algo, optimizer="sgd",
                                     lr=0.05))
    ones = jnp.ones(cfg.n_workers, bool)
    batch = _batch(cfg)
    losses = []
    for _ in range(3):
        m = t.step(batch, ones, ones)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses), (algo, losses)
    assert t.rounds == 3
    # the state is the one canonical FlatTrainState
    assert t.state.params.shape == (t.engine.P,)


def test_fedbuff_gate_holds_optimizer():
    """FedBuff's applied gate: with one committing worker per round and
    buffer_size=3, params must stay EXACTLY put for two rounds and move on
    the third."""
    cfg = _tiny_cfg()
    t = Trainer.create(TrainerConfig(arch=cfg, algo="fedbuff",
                                     fedbuff_buffer_size=3, lr=0.05))
    n = cfg.n_workers
    one = jnp.zeros(n, bool).at[0].set(True)
    batch = _batch(cfg)
    p0 = np.asarray(t.state.params)
    m1 = t.step(batch, one, one)
    m2 = t.step(batch, one, one)
    held = np.asarray(t.state.params)
    m3 = t.step(batch, one, one)
    assert float(m1["applied"]) == 0.0 and float(m2["applied"]) == 0.0
    assert float(m3["applied"]) == 1.0
    np.testing.assert_array_equal(held, p0)           # gate held
    assert np.any(np.asarray(t.state.params) != p0)   # flush applied
    assert int(t.state.opt.step) == 1                 # only flushes count


# ------------------------------------- registry == simulator rule (math)


@pytest.mark.parametrize("name", ["sync_sgd", "mifa"])
def test_round_algo_matches_simulator_rule(name):
    """The production RoundAlgo and the simulator's on_round are the same
    rule: N rounds with identical stacked gradients and masks produce
    bit-identical params (eager, flat sgd vs per-leaf sgd)."""
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    n, lr = 5, 0.07
    spec = make_flat_spec(tree)
    engine = DuDeEngine(spec=spec, n_workers=n, interpret=True)
    algo = make_round_algo(name, engine)
    sim = make_algo(name, n)

    srv = algo.init()
    sim_state = sim.init_state(jax.tree.map(jnp.zeros_like, tree))
    pf = spec.ravel(tree)
    params = tree
    for r in range(4):
        stacked = jax.tree.map(
            lambda x: jnp.asarray(
                rng.normal(size=(n,) + x.shape), jnp.float32), tree)
        mask = jnp.asarray(rng.random(n) < 0.7)
        fresh = spec.ravel_stacked(stacked)
        srv, g, applied = algo.round(srv, fresh, mask, mask)
        assert bool(applied)
        pf = pf - lr * g
        sim_state, params, _ = sim.on_round(sim_state, stacked, mask,
                                            params, lr)
    back = spec.unravel(pf)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]),
                                      err_msg=f"{name}/{k}")


def test_fedbuff_round_rule_reference():
    """Round-mode FedBuff against a numpy reference: accumulate committing
    rows, flush at buffer_size with the mean over the actual count."""
    rng = np.random.default_rng(1)
    n, P0, bs = 4, 6, 3
    spec = make_flat_spec(jnp.zeros(P0))
    P = spec.padded_size
    engine = DuDeEngine(spec=spec, n_workers=n, interpret=True)
    algo = make_round_algo("fedbuff", engine, buffer_size=bs)
    st = algo.init()
    acc_ref = np.zeros(P, np.float32)
    cnt_ref = 0
    for r in range(6):
        fresh = jnp.asarray(rng.normal(size=(n, P)), jnp.float32)
        cm = jnp.asarray(rng.random(n) < 0.5)
        st, g, applied = algo.round(st, fresh, cm, cm)
        acc_ref = acc_ref + np.sum(np.asarray(fresh)
                                   * np.asarray(cm)[:, None], axis=0)
        cnt_ref += int(np.sum(np.asarray(cm)))
        flush = cnt_ref >= bs
        assert bool(applied) == flush, r
        if flush:
            np.testing.assert_allclose(np.asarray(g),
                                       acc_ref / max(cnt_ref, 1),
                                       rtol=1e-5, atol=1e-6)
            acc_ref[:] = 0.0
            cnt_ref = 0
        np.testing.assert_allclose(np.asarray(st[0]), acc_ref,
                                   rtol=1e-5, atol=1e-6)
        assert int(st[1]) == cnt_ref


# --------------------------------------------------- auto-format restore


def test_trainer_checkpoint_roundtrip_flat(tmp_path):
    """Trainer.save -> Trainer.restore: flat directory auto-dispatches and
    the FULL state (params, slots, server slabs) restores bit-for-bit."""
    cfg = _tiny_cfg()
    config = TrainerConfig(arch=cfg, algo="dude", optimizer="adamw", lr=0.01)
    t = Trainer.create(config)
    ones = jnp.ones(cfg.n_workers, bool)
    for _ in range(2):
        t.step(_batch(cfg), ones, ones)
    t.save(str(tmp_path))
    t2 = Trainer.restore(str(tmp_path), config)
    for a, b in zip(jax.tree.leaves(t.state), jax.tree.leaves(t2.state)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_trainer_restore_resumes_round_counter(tmp_path):
    """Post-resume periodic saves must continue the step sequence: restore
    picks the checkpoint's step up as the session round, so a later save
    never rewinds below (and silently loses to) the restored step."""
    cfg = _tiny_cfg()
    config = TrainerConfig(arch=cfg, algo="dude",
                           checkpoint=CheckpointPolicy(directory=str(tmp_path),
                                                       every=2))
    t = Trainer.create(config)
    ones = jnp.ones(cfg.n_workers, bool)
    for _ in range(4):
        t.step(_batch(cfg), ones, ones)
        t.maybe_save()
    t2 = Trainer.restore(str(tmp_path), config)      # loads step_4
    assert t2.rounds == 4
    t2.step(_batch(cfg), ones, ones)
    t2.step(_batch(cfg), ones, ones)
    assert t2.maybe_save() is not None               # writes step_6, not 2
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 6
    t3 = Trainer.restore(str(tmp_path), config, step=4)
    assert t3.rounds == 4


def test_trainer_restore_legacy_pytree(tmp_path):
    """Trainer.restore on a LEGACY pytree (params-only) directory: the same
    one call auto-dispatches, ravels the params slab bit-for-bit, and keeps
    fresh slots/server state."""
    from repro.checkpoint import save_checkpoint
    from repro.models import lm_init
    cfg = _tiny_cfg()
    params = lm_init(jax.random.PRNGKey(3), cfg)
    save_checkpoint(str(tmp_path), 7, params)      # legacy format
    config = TrainerConfig(arch=cfg, algo="dude")
    t = Trainer.restore(str(tmp_path), config)
    np.testing.assert_array_equal(
        np.asarray(t.state.params),
        np.asarray(t.engine.spec.ravel(params, jnp.float32)))
    assert float(jnp.max(jnp.abs(t.state.engine.g_bar))) == 0.0


def test_restore_params_auto_dispatch(tmp_path):
    """checkpoint.restore_params reads BOTH formats into a params pytree."""
    from repro.checkpoint import restore_params, save_checkpoint
    from repro.launch.steps import init_flat_train_state
    rng = np.random.default_rng(2)
    tree = _tree(rng)
    spec = make_flat_spec(tree)
    eng = DuDeEngine(spec=spec, n_workers=3, interpret=True)
    state = init_flat_train_state(eng, sgd(0.1), tree)
    save_checkpoint(str(tmp_path / "flat"), 1, state, flat_spec=spec)
    save_checkpoint(str(tmp_path / "tree"), 1, tree)
    for d in ("flat", "tree"):
        back = restore_params(str(tmp_path / d), 1, tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]), err_msg=d)


def test_serve_session_from_trainer_checkpoint(tmp_path):
    """A model trained through Trainer serves from its flat checkpoint with
    no format plumbing: ServeSession.create(ckpt_dir=...)."""
    cfg = _tiny_cfg()
    t = Trainer.create(TrainerConfig(arch=cfg, algo="dude"))
    ones = jnp.ones(cfg.n_workers, bool)
    t.step(_batch(cfg), ones, ones)
    t.save(str(tmp_path))
    s = ServeSession.create(
        ServeConfig(arch=cfg, batch=2, max_len=24, cache_dtype=jnp.float32),
        ckpt_dir=str(tmp_path))
    for a, b in zip(jax.tree.leaves(s.params), jax.tree.leaves(t.params())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (2, 8),
                                            0, cfg.vocab_size)}
    gen = s.generate(prompts, gen_len=4)
    assert gen.shape == (2, 4)


# ------------------------------------------------------- migration shim


def test_flat_state_from_legacy_tuple():
    """A held pytree-mode (params, opt_state, dude_state) tuple — produced
    by the RETIRED tuple step of an old release — converts once to the
    canonical FlatTrainState and continues through the flat step."""
    from repro.launch.steps import (
        flat_state_from_legacy, make_engine, make_train_step)
    from repro.models import lm_init
    from repro.optim import momentum_sgd
    cfg = _tiny_cfg()
    opt = momentum_sgd(0.05)
    engine = make_engine(cfg)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    ones = jnp.ones(cfg.n_workers, bool)
    # re-enact one old-style tuple update by hand (the retired step was
    # exactly: engine.round -> unravel -> pytree opt.apply)
    rng = np.random.default_rng(0)
    fresh = jnp.asarray(rng.normal(size=(cfg.n_workers, engine.P)),
                        jnp.float32)
    dude_state, g_flat = engine.round(engine.init(), fresh, ones, ones)
    params, opt_state = opt.apply(params, engine.spec.unravel(g_flat),
                                  opt.init(params))
    state = flat_state_from_legacy(engine, opt, params, opt_state, dude_state)
    np.testing.assert_array_equal(
        np.asarray(state.params),
        np.asarray(engine.spec.ravel(params, jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(state.opt.slots),
        np.asarray(engine.spec.ravel(opt_state.slots, jnp.float32)))
    fstep = jax.jit(make_train_step(cfg, None, opt, engine=engine))
    state, metrics = fstep(state, _batch(cfg), ones, ones)
    assert np.isfinite(float(metrics["loss"]))


# --------------------------------------------------- lowering / dryrun


def test_trainer_abstract_input_specs_and_lower():
    """input_specs covers the full step signature and the session lowers
    with its shardings (the dryrun path, in miniature)."""
    cfg = _tiny_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    for algo in ("dude", "fedbuff"):
        session = Trainer.abstract(TrainerConfig(arch=cfg, algo=algo,
                                                 mesh=mesh))
        shapes, shardings = session.input_specs("train_4k")
        assert len(shapes) == 4 and len(shardings) == 4
        st = shapes[0]
        assert st.params.shape == (session.engine.P,)
        compiled = session.lower("train_4k").compile()
        assert compiled.cost_analysis() is not None


def test_abstract_session_has_no_state():
    t = Trainer.abstract(TrainerConfig(arch=_tiny_cfg()))
    assert t.state is None
    with pytest.raises(ConfigError, match="abstract"):
        t.step(_batch(_tiny_cfg()), jnp.ones(4, bool), jnp.ones(4, bool))


def test_flat_step_serves_baseline_algos_directly():
    """With the pytree fork retired, make_train_step hands ANY registry
    rule the same flat signature — no DuDe-only carve-out left."""
    from repro.launch.steps import (
        init_flat_train_state, make_engine, make_train_step)
    from repro.models import lm_init
    cfg = _tiny_cfg()
    engine = make_engine(cfg)
    algo = make_round_algo("mifa", engine)
    step = make_train_step(cfg, None, sgd(0.05), engine=engine, algo=algo)
    state = init_flat_train_state(engine, sgd(0.05),
                                  lm_init(jax.random.PRNGKey(0), cfg),
                                  algo=algo)
    ones = jnp.ones(cfg.n_workers, bool)
    state, metrics = jax.jit(step)(state, _batch(cfg), ones, ones)
    assert np.isfinite(float(metrics["loss"]))
    assert state.engine.shape == (cfg.n_workers, engine.P)  # mifa memory
