"""Sharding-layer tests.

Spec-level checks run in-process; the compile-level check (train_step lowers
and runs on a real multi-device mesh) runs in a SUBPROCESS because the
device-count override must be set before jax initializes (the main pytest
process stays single-device for the smoke tests)."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_production_mesh  # noqa: F401 (import check)
from repro.sharding.specs import param_spec


class _FakeMesh:
    shape = {"data": 16, "model": 16}


def test_param_spec_rules():
    mesh = _FakeMesh()
    assert param_spec("stack/groups/0/attn/wq/kernel", (4096, 4096), mesh) == \
        P("data", "model")
    assert param_spec("stack/groups/0/attn/wo/kernel", (4096, 4096), mesh) == \
        P("model", "data")
    assert param_spec("embed/embedding", (32000, 4096), mesh) == P("model", "data")
    assert param_spec("stack/groups/0/moe/wup", (64, 2048, 1024), mesh) == \
        P("model", "data", None)
    # indivisible dims are dropped, not crashed
    assert param_spec("x/attn/wq/kernel", (33, 47), mesh) == P(None, None)
    # stacked group leaves get a leading None
    assert param_spec("stack/groups/0/mlp/up/kernel", (24, 896, 4864), mesh,
                      stacked=True) == P(None, "data", "model")
    # norm scales replicate
    assert param_spec("stack/groups/0/ln1/scale", (4096,), mesh) == P(None)


SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.configs import get_config
from repro.core.dude import DuDeConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_engine, make_train_step, train_batch_specs, abstract_train_state
from repro.models import lm_init
from repro.optim import sgd
import numpy as np

cfg = get_config("qwen2_0_5b").smoke()
mesh = make_mesh((2, 4), ("data", "model"))
n = cfg.n_workers
dude_cfg = DuDeConfig(n, jnp.float32)
with mesh:
    st_shapes, st_sh = abstract_train_state(cfg, mesh, dude_cfg=dude_cfg)
    engine = make_engine(cfg, mesh, dude_cfg)
    opt = sgd(0.01)
    step = make_train_step(cfg, mesh, opt, dude_cfg=dude_cfg, engine=engine)
    # real (non-abstract) flat state, P-axis sharded by init_flat_train_state
    from repro.launch.steps import init_flat_train_state
    state = init_flat_train_state(engine, opt,
                                  lm_init(jax.random.PRNGKey(0), cfg))
    assert state.params.sharding == st_sh.params
    key = jax.random.PRNGKey(1)
    S = 64
    batch = {
        "tokens": jax.random.randint(key, (n, 2, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (n, 2, S), 0, cfg.vocab_size),
    }
    ones = jnp.ones(n, bool)
    jitted = jax.jit(step)
    for _ in range(3):
        state, metrics = jitted(state, batch, ones, ones)
    loss = float(metrics["loss"])
    finite = bool(jnp.isfinite(loss))
    print(json.dumps({"loss": loss, "finite": finite,
                      "ndev": jax.device_count()}))
"""


def test_train_step_runs_on_multidevice_mesh():
    r = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ndev"] == 8
    assert out["finite"]
