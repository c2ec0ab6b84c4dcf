"""TP-native unravel acceptance tests (docs/engine.md, "TP-native unravel").

Proves, on an 8-device (data, model) host mesh, that the ppermute-ring
exchange paths are BIT-FOR-BIT equal to the replicated oracle in both
directions — ``unravel_sharded`` == ``unravel`` on mixed-dtype trees with a
pad tail and leaves straddling P-shard boundaries, and
``ravel_stacked_sharded`` == ``ravel_stacked`` — on handcrafted layouts and
on a real architecture's ``param_shardings``; that the compiled exchange
(and the whole ``params_layout="tp"`` train step) contains NO tensor of
``P`` or more elements while the replicated step does (detector sanity);
and that the tp step tracks the replicated step across optimizer steps for
every engine backend (first-step losses bitwise equal — the forward from
TP shards is deterministic — params to tight tolerance thereafter, since
GSPMD regroups the backward matmul reductions when params enter sharded).

The in-process tests need >= 8 devices, so on a single-device run they are
skipped and ``test_tp_suite_subprocess`` re-runs them under
``--xla_force_host_platform_device_count=8`` (same driver pattern as
test_engine_sharded.py).  CI additionally runs this file in-process under
the 8-device override.
"""

import os
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import NDEV, collective_counts, multidevice
from repro.core.flatten import make_flat_spec
from repro.launch.mesh import make_mesh

N_STACK = 3  # worker dim for the reverse-path tests


def dm_mesh():
    """The (data=2, model=4) mesh the TP suite runs on (8 devices)."""
    return make_mesh((2, 4), ("data", "model"))


def _tree(rng):
    """Mixed-dtype tree exercising every exchange case: a leaf sharded on
    BOTH mesh axes, a stacked leaf, a tiny replicated leaf (odd size => the
    flat vector gets a pad tail), and a bf16 leaf — with leaf boundaries
    falling inside P-shards (W=256 here, 'emb' spans shards 0..2)."""
    return {
        "emb": jnp.asarray(rng.normal(size=(48, 16)), jnp.float32),
        "stk": jnp.asarray(rng.normal(size=(3, 8, 16)), jnp.float32),
        "norm": jnp.asarray(rng.normal(size=(7,)), jnp.float32),
        "b16": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32
                           ).astype(jnp.bfloat16),
    }


def _shardings(mesh):
    return {
        "emb": NamedSharding(mesh, P("model", "data")),
        "stk": NamedSharding(mesh, P(None, "data", "model")),
        "norm": NamedSharding(mesh, P()),
        "b16": NamedSharding(mesh, P("model", None)),
    }


def _spec_plan(mesh):
    from repro.sharding import flat_vec_sharding
    tree = _tree(np.random.default_rng(0))
    spec = make_flat_spec(tree, mesh_axis_size=NDEV)
    plan = spec.tp_plan(mesh, _shardings(mesh), axes=("data", "model"))
    return tree, spec, plan, flat_vec_sharding(spec, mesh, ("data", "model"))


# --------------------------------------------------- exchange == oracle


@multidevice
def test_unravel_sharded_matches_unravel():
    """Forward exchange: P-shards -> TP-layout leaves, bit-for-bit equal to
    slicing the gathered vector, per-leaf dtypes restored (incl. bf16),
    despite the pad tail and shard-straddling leaf boundaries."""
    mesh = dm_mesh()
    tree, spec, plan, vec_sh = _spec_plan(mesh)
    assert spec.padded_size > spec.size  # the pad tail is real
    flat = jax.device_put(spec.ravel(tree), vec_sh)
    got = jax.jit(lambda f: spec.unravel_sharded(f, mesh, plan=plan))(flat)
    want = spec.unravel(spec.ravel(tree))
    for k in tree:
        assert got[k].dtype == want[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(want[k], np.float32))
    # cast=False keeps the slab dtype (the raw path the forward may use)
    raw = jax.jit(lambda f: spec.unravel_sharded(
        f, mesh, plan=plan, cast=False))(flat)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(raw))


@multidevice
def test_ravel_stacked_sharded_matches_ravel_stacked():
    """Reverse exchange: TP-layout stacked leaves -> [n, P] slab shards,
    bit-for-bit (pure scatters of disjoint positions — signed zeros and all),
    pad lanes zero."""
    mesh = dm_mesh()
    tree, spec, plan, _ = _spec_plan(mesh)
    stree = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(N_STACK)]), tree)
    # oracle BEFORE placement: eager ravel of TP-placed leaves would round-
    # trip through the GSPMD partitioner, which miscompiles reshape+concat
    # over mixed 2-D-sharded operands on this jax version (the bug the
    # shard_map ring sidesteps)
    want = spec.ravel_stacked(stree)
    stree = jax.device_put(stree, {
        k: NamedSharding(mesh, P(None, *sh.spec))
        for k, sh in _shardings(mesh).items()})
    got = jax.jit(lambda t: spec.ravel_stacked_sharded(
        t, mesh, plan=plan))(stree)
    assert got.shape == (N_STACK, spec.padded_size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.any(np.asarray(got)[:, spec.size:])  # pads stay zero


@multidevice
def test_exchange_bitexact_real_arch():
    """Both directions on a real architecture's ``param_shardings`` (the
    Megatron-TP layouts the train step actually feeds): still bit-for-bit."""
    from repro.configs import get_config
    from repro.models import lm_init
    from repro.sharding import flat_vec_sharding, param_shardings

    cfg = get_config("qwen2_0_5b").smoke()
    mesh = dm_mesh()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    spec = make_flat_spec(params, mesh_axis_size=NDEV)
    p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
    plan = spec.tp_plan(mesh, p_sh, axes=("data", "model"))

    flat = jax.device_put(spec.ravel(params),
                          flat_vec_sharding(spec, mesh, ("data", "model")))
    got = jax.jit(lambda f: spec.unravel_sharded(f, mesh, plan=plan))(flat)
    want = spec.unravel(spec.ravel(params))
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves_with_path(want)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=jax.tree_util.keystr(ka))

    stree = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(N_STACK)]), params)
    got = jax.jit(lambda t: spec.ravel_stacked_sharded(
        t, mesh, plan=plan))(stree)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(spec.ravel_stacked(stree)))


# ------------------------------------------------- the memory contract


@multidevice
def test_unravel_hlo_no_full_p_tensor():
    """The compiled forward exchange must contain NO tensor of >= P
    elements (each device only ever holds its window + the circulating one
    + its TP blocks) and must move data via collective-permute, not
    all-gather.  The replicated oracle DOES materialize a full [P] buffer —
    detector sanity."""
    from repro.launch.hlo_analysis import full_p_tensors

    mesh = dm_mesh()
    tree, spec, plan, vec_sh = _spec_plan(mesh)
    flat = jax.device_put(spec.ravel(tree), vec_sh)

    hlo_tp = jax.jit(lambda f: spec.unravel_sharded(f, mesh, plan=plan)
                     ).lower(flat).compile().as_text()
    assert full_p_tensors(hlo_tp, spec.padded_size) == []
    counts = collective_counts(hlo_tp)
    assert counts["collective-permute"] >= 1, counts
    assert counts["all-gather"] == 0, counts

    repl = NamedSharding(mesh, P())
    hlo_repl = jax.jit(lambda f: spec.unravel(
        jax.lax.with_sharding_constraint(f, repl))
    ).lower(flat).compile().as_text()
    assert full_p_tensors(hlo_repl, spec.padded_size) != []


@multidevice
def test_tp_plan_analytics():
    """The plan's analytic memory story: per-device peak is O(P/k + blocks),
    strictly below the replicated O(P) footprint, and every per-leaf gather
    is bounded by that leaf's segment (never P)."""
    mesh = dm_mesh()
    _, spec, plan, _ = _spec_plan(mesh)
    assert plan.k == NDEV
    assert plan.window == spec.padded_size // NDEV
    assert plan.full_vector_bytes == 4 * spec.padded_size
    assert plan.peak_bytes < plan.full_vector_bytes
    assert plan.ring_bytes == (plan.k - 1) * plan.window_bytes
    seg_bytes = plan.max_leaf_segment_bytes()
    assert 0 < seg_bytes <= 4 * max(spec.sizes)
    for lf in plan.leaves:
        assert lf.block_size * 4 <= 4 * spec.sizes[lf.index]


# ----------------------------------------------------- full train step


def _run_steps(cfg, mesh, layout, backend, batch, n_steps=3):
    from repro.core.dude import DuDeConfig
    from repro.launch.steps import (TrainOptions, init_flat_train_state,
                                    make_engine, make_train_step)
    from repro.models import lm_init
    from repro.optim import sgd

    n = cfg.n_workers
    dude_cfg = DuDeConfig(n, jnp.float32)
    options = TrainOptions(params_layout=layout, backend=backend)
    ones = jnp.ones(n, bool)
    with mesh:
        engine = make_engine(cfg, mesh, dude_cfg, options)
        opt = sgd(0.01)
        step = jax.jit(make_train_step(cfg, mesh, opt, dude_cfg=dude_cfg,
                                       options=options, engine=engine))
        state = init_flat_train_state(
            engine, opt, lm_init(jax.random.PRNGKey(0), cfg))
        b_sh = NamedSharding(mesh, P(None, "data", None))
        sb = jax.tree.map(lambda x: jax.device_put(x, b_sh), batch)
        hlo = step.lower(state, sb, ones, ones).compile().as_text()
        losses = []
        for _ in range(n_steps):
            state, metrics = step(state, sb, ones, ones)
            losses.append(float(metrics["loss"]))
    return np.asarray(state.params), losses, hlo, engine.P


@multidevice
@pytest.mark.parametrize("backend", ["reference", "indexed", "pallas"])
def test_tp_step_matches_replicated(backend):
    """params_layout='tp' vs 'replicated' on the full train step, per
    engine backend: the first-step losses are BITWISE equal (the forward
    fed from TP shards is deterministic given identical params); after a
    few optimizer steps params agree to tight tolerance — not bitwise,
    because GSPMD partitions the backward matmul contractions differently
    when params enter TP-sharded (partial-K + psum reorders the reduction).
    The tp step's HLO must hold no full-[P] tensor; the replicated step's
    must (the memory claim is about the layout, not the backend)."""
    from repro.configs import get_config
    from repro.launch.hlo_analysis import full_p_tensors

    cfg = get_config("qwen2_0_5b").smoke()
    mesh = dm_mesh()
    n = cfg.n_workers
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size),
    }
    p_repl, l_repl, hlo_repl, engP = _run_steps(
        cfg, mesh, "replicated", backend, batch)
    p_tp, l_tp, hlo_tp, _ = _run_steps(cfg, mesh, "tp", backend, batch)

    assert l_tp[0] == l_repl[0]          # bitwise: same params, det. forward
    np.testing.assert_allclose(l_tp, l_repl, rtol=2e-2)
    np.testing.assert_allclose(p_tp, p_repl, atol=5e-3, rtol=1e-3)

    assert full_p_tensors(hlo_tp, engP) == []
    assert collective_counts(hlo_tp)["collective-permute"] >= 2  # both rings
    assert full_p_tensors(hlo_repl, engP) != []


# -------------------------------------------- plumbing and validation


def test_params_layout_validation():
    """Misconfiguration fails loudly at construction time, not trace time."""
    from repro.api import ConfigError, TrainerConfig
    from repro.launch.steps import TrainOptions, make_train_step
    from repro.configs import get_config

    with pytest.raises(ValueError, match="params_layout"):
        TrainOptions(params_layout="bogus")
    with pytest.raises(ConfigError, match="params_layout"):
        TrainerConfig(arch="qwen2_0_5b", smoke=True, params_layout="nope")
    with pytest.raises(ConfigError, match="needs a mesh"):
        TrainerConfig(arch="qwen2_0_5b", smoke=True, params_layout="tp")
    cfg = get_config("qwen2_0_5b").smoke()
    with pytest.raises(ValueError, match="mesh-native engine"):
        make_train_step(cfg, mesh=None,
                        options=__import__("repro.launch.steps",
                                           fromlist=["TrainOptions"]
                                           ).TrainOptions(params_layout="tp"))


def test_engine_tp_plan_needs_mesh():
    from repro.core.engine import DuDeEngine

    eng = DuDeEngine.for_tree({"w": jnp.zeros(4)}, 2)
    with pytest.raises(ValueError, match="mesh"):
        eng.tp_plan({"w": None})


@multidevice
def test_tp_plan_cached_and_validated():
    """Same (spec, mesh, shardings) -> the SAME plan object (the exchange
    plan is static geometry, built once); a leaf sharded on an axis outside
    the P-axis group is rejected."""
    from repro.sharding import flat_to_tp_plan

    mesh = dm_mesh()
    tree, spec, plan, _ = _spec_plan(mesh)
    again = flat_to_tp_plan(spec, mesh, _shardings(mesh),
                            axes=("data", "model"))
    assert again is plan
    with pytest.raises(ValueError, match="outside"):
        flat_to_tp_plan(spec, mesh, _shardings(mesh), axes=("data",))


@multidevice
def test_segment_cache_memoized():
    """Satellite: ``shard_segments`` is memoized per spec instance and the
    memo returns the identical tuple."""
    tree = _tree(np.random.default_rng(0))
    spec = make_flat_spec(tree, mesh_axis_size=NDEV)
    first = spec.shard_segments(3)
    assert spec.shard_segments(3) is first


def test_warn_unsplittable_names_leaf_once():
    """Satellite: the constrain_grads fallback warns ONCE per (shapes, D)
    key, naming the offending leaf shape."""
    from repro.launch.steps import _WARNED_UNSPLITTABLE, _warn_unsplittable

    _WARNED_UNSPLITTABLE.clear()
    batch = {"tokens": jnp.zeros((4, 3, 8)), "labels": jnp.zeros((4, 4, 8))}
    with pytest.warns(RuntimeWarning, match=r"\(4, 3, 8\)"):
        _warn_unsplittable(batch, 2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a second warn would raise
        _warn_unsplittable(batch, 2)
    with pytest.warns(RuntimeWarning):   # new key => new warning
        _warn_unsplittable(batch, 4)


# ------------------------------------------------------ subprocess driver


def test_tp_suite_subprocess():
    """Run the in-process tests above on 8 host-platform devices (they are
    skipped in a default single-device session)."""
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
         str(Path(__file__).resolve()), "-k", "not subprocess"],
        capture_output=True, text=True, timeout=540, env=env, cwd=repo,
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "skipped" not in r.stdout.splitlines()[-1], r.stdout[-500:]
