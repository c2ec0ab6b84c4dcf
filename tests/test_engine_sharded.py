"""Sharded ServerEngine acceptance tests.

Proves, for all three backends, that a P-axis sharded ``EngineState`` on an
8-device host-platform mesh matches the single-device engine bit-for-bit on
``g_bar`` (and up to buffer-dtype rounding on the slabs), that the sharded
round needs no collective at all, and that the ``constrain_grads`` train
path emits a true reduce-scatter for the gradient->buffer path — not
all-reduce + dynamic-slice.

The in-process tests need >= 8 devices, so on a normal single-device run
they are skipped and ``test_sharded_suite_subprocess`` re-runs them in a
subprocess with ``--xla_force_host_platform_device_count=8`` (the device
count must be set before jax initializes — same trick as test_sharding.py).
CI additionally runs this file in-process under the 8-device override.
"""

import os
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

def _tree(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(13, 17)), jnp.float32),
        "emb": jnp.asarray(rng.normal(size=(4, 3, 9)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=5), jnp.float32),
    }


def _engines(backend, buf_dtype, n, mesh):
    spec = make_flat_spec(_tree(np.random.default_rng(0)),
                          mesh_axis_size=NDEV)
    kw = dict(spec=spec, n_workers=n, buffer_dtype=buf_dtype,
              backend=backend, interpret=True)
    return (DuDeEngine(**kw),
            DuDeEngine(**kw, mesh=mesh, axis_name="p"))


# ------------------------------------------------- sharded == unsharded


@multidevice
@pytest.mark.parametrize("buf_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", BACKENDS)
def test_round_sharded_matches_unsharded(backend, buf_dtype):
    """P-axis sharded round == single-device round: bit-for-bit on g_bar,
    buffer-dtype rounding on the slabs (they agree bitwise here too — the
    round is elementwise on P, so sharding cannot reorder anything)."""
    rng = np.random.default_rng(3)
    n = 5
    mesh = p_mesh()
    eng_u, eng_s = _engines(backend, buf_dtype, n, mesh)
    P = eng_u.P
    assert eng_s.shard_P == P // NDEV
    su = eng_u.init()._replace(
        g_workers=jnp.asarray(rng.normal(size=(n, P)), buf_dtype),
        inflight=jnp.asarray(rng.normal(size=(n, P)), buf_dtype))
    ss = jax.device_put(su, eng_s.shardings())
    step_u, step_s = jax.jit(eng_u.round), jax.jit(eng_s.round)
    for t in range(6):
        fresh = jnp.asarray(rng.normal(size=(n, P)), jnp.float32)
        sm = jnp.asarray(rng.random(n) < 0.5)
        cm = jnp.asarray(rng.random(n) < 0.4)
        su, gu = step_u(su, fresh, sm, cm)
        ss, gs = step_s(ss, fresh, sm, cm)
        np.testing.assert_array_equal(np.asarray(gu), np.asarray(gs))
        for a, b in ((su.g_workers, ss.g_workers),
                     (su.inflight, ss.inflight)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        np.testing.assert_array_equal(np.asarray(su.acc_count),
                                      np.asarray(ss.acc_count))


@multidevice
@pytest.mark.parametrize("backend", BACKENDS)
def test_commit_sharded_matches_unsharded(backend):
    rng = np.random.default_rng(5)
    n = 4
    mesh = p_mesh()
    eng_u, eng_s = _engines(backend, jnp.float32, n, mesh)
    P = eng_u.P
    su = eng_u.init()._replace(
        g_workers=jnp.asarray(rng.normal(size=(n, P)), jnp.float32))
    ss = jax.device_put(su, eng_s.shardings())
    cu, cs = jax.jit(eng_u.commit), jax.jit(eng_s.commit)
    for t in range(5):
        g = jnp.asarray(rng.normal(size=P), jnp.float32)
        su, gu = cu(su, jnp.int32(t % n), g)
        ss, gs = cs(ss, jnp.int32(t % n), g)
        np.testing.assert_array_equal(np.asarray(gu), np.asarray(gs))
        np.testing.assert_array_equal(np.asarray(su.g_workers),
                                      np.asarray(ss.g_workers))


@multidevice
@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_round_moves_no_bytes(backend):
    """The round is elementwise on P (worker-sum local to each P-shard):
    the compiled sharded round must contain ZERO collective ops."""
    n = 4
    mesh = p_mesh()
    _, eng_s = _engines(backend, jnp.float32, n, mesh)
    state = eng_s.init()
    fresh = jax.device_put(jnp.ones((n, eng_s.P), jnp.float32),
                           eng_s.shardings().g_workers)
    ones = jnp.ones(n, bool)
    hlo = jax.jit(eng_s.round).lower(state, fresh, ones, ones
                                     ).compile().as_text()
    counts = {k: v for k, v in collective_counts(hlo).items() if v}
    assert not counts, counts


# ------------------------------------- gradient -> buffer reduce-scatter


@multidevice
def test_constrain_grads_emits_reduce_scatter():
    """With constrain_grads=True the gradient->buffer path must lower to a
    reduce-scatter into the owned P-shard; the unconstrained baseline (and
    everything GSPMD does on its own) emits no reduce-scatter at all.  The
    two variants must agree numerically."""
    from repro.configs import get_config
    from repro.core.dude import DuDeConfig
    from repro.launch.steps import (TrainOptions, make_engine,
                                    make_train_step)
    from repro.models import lm_init
    from repro.optim import sgd
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config("qwen2_0_5b").smoke()
    mesh = make_mesh((2, 4), ("data", "model"))
    n = cfg.n_workers
    dude_cfg = DuDeConfig(n, jnp.float32)
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, 4, 32), 0, cfg.vocab_size),
    }
    ones = jnp.ones(n, bool)
    results = {}
    counts = {}
    for constrain in (False, True):
        options = TrainOptions(constrain_grads=constrain)
        with mesh:
            from repro.launch.steps import init_flat_train_state
            engine = make_engine(cfg, mesh, dude_cfg, options)
            opt = sgd(0.01)
            step = jax.jit(make_train_step(cfg, mesh, opt, dude_cfg=dude_cfg,
                                           options=options, engine=engine))
            state = init_flat_train_state(
                engine, opt, lm_init(jax.random.PRNGKey(0), cfg))
            b_sh = NamedSharding(mesh, P(None, "data", None))
            sharded_batch = jax.tree.map(
                lambda x: jax.device_put(x, b_sh), batch)
            hlo = step.lower(state, sharded_batch,
                             ones, ones).compile().as_text()
            counts[constrain] = collective_counts(hlo)
            for _ in range(2):
                state, metrics = step(state, sharded_batch, ones, ones)
            results[constrain] = float(metrics["loss"])
    assert counts[False]["reduce-scatter"] == 0, counts[False]
    assert counts[True]["reduce-scatter"] >= 1, counts[True]
    # fewer all-reduces: the data-axis gradient reduction moved into the
    # reduce-scatter instead of all-reduce + slice
    assert counts[True]["all-reduce"] < counts[False]["all-reduce"], counts
    assert np.isfinite(results[True])
    np.testing.assert_allclose(results[True], results[False], atol=1e-4)


# ------------------------------------------------------ subprocess driver


def test_sharded_suite_subprocess():
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
