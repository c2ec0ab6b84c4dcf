"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phase functions agree across the pallas and reference backends at smoke
size (Pallas in interpret mode) — on the TPU's multi-group kernel grid too,
and P-sharded on four host devices.  Also the compile-cache location rule
the entry points share (``repro.launch.cache``)."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC, **kw)
    return env


def test_chip_smoke_refuses_cpu():
    """No TPU: non-zero exit before any model is built, and no result."""
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "[config]" not in r.stdout
    assert '"ok"' not in r.stdout


def test_chip_smoke_phases_agree_at_smoke_size(smoke):
    arch = dataclasses.replace(
        smoke.model_config(2, base=get_config("qwen2_0_5b").smoke()),
        ce_chunk=16)
    inputs = smoke.round_inputs(arch, 3, 32)
    # the schedule must commit inside the window, or the params never move
    assert any(cm.any() for _, _, cm in inputs)
    a = smoke.run_rounds(smoke.trainer_config(arch, "pallas"), inputs)
    b = smoke.run_rounds(smoke.trainer_config(arch, "reference"), inputs)
    assert not a["kernel_in_hlo"]          # interpret mode on the CPU
    assert a["grid"] == (1, 1)
    assert a["losses"] == b["losses"]
    assert np.isfinite(a["params"]).all()
    assert np.max(np.abs(a["params"] - b["params"])) <= smoke.PARAM_TOL
    assert min(a["moved"], b["moved"]) >= smoke.MIN_MOVE

    c = smoke.run_arrivals(smoke.trainer_config(arch, "pallas"), 3, 32)
    assert c["iters"] == 3 and c["params_finite"]


@pytest.mark.parametrize("case", range(3), ids=["dense", "int8", "sparse"])
def test_chip_smoke_row_groups_agree(smoke, monkeypatch, case):
    """Phase (d) on the TPU's grid: 8-row groups forced in interpret mode,
    so the kernels carry the worker sum across two groups in the VMEM
    accumulator and write g_bar/params/slots at the last one."""
    from repro.kernels import dude_update
    monkeypatch.setattr(dude_update, "_row_block",
                        lambda n, interpret: 8 if n % 8 == 0 else n)
    d = smoke.run_row_groups(16, 4096 + 640, smoke.GROUP_ROUNDS,
                             *smoke.GROUP_CASES[case])
    assert d["grid"] == (1, 2)
    assert d["g_bar_diff"] <= smoke.GROUP_TOL
    assert d["params_diff"] <= smoke.GROUP_TOL
    assert d["moved"] >= 1e3 * smoke.GROUP_TOL


_SHARDED_PROG = """
import dataclasses, importlib.util, sys
import jax
from repro.configs import get_config
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
arch = dataclasses.replace(
    cs.model_config(2, base=get_config("qwen2_0_5b").smoke()), ce_chunk=16)
s, u, rel, diff = cs.compare_sharded(arch, 32, jax.devices())
print(rel, diff, u["moved"])
"""


def test_chip_smoke_sharded_comparison():
    """The ``--chips 4`` comparison on four host devices at smoke size: the
    1x4 P-sharded step (TP-native feed) against the unsharded one."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_PROG, str(ROOT / "chip_smoke.py")],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    rel, diff, moved = r.stdout.split()[-3:]
    rel, diff, moved = float(rel), float(diff), float(moved)
    smoke = _load()
    assert rel <= smoke.SHARDED_LOSS_RTOL
    assert moved >= smoke.MIN_MOVE
    assert diff <= smoke.SHARDED_PARAM_RTOL * moved


_CACHE_PROG = """
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print(use_compile_cache(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_location(tmp_path, env_set):
    """Unset: the cache lands in ``<root>/.jax_cache``.  Set: it lands in
    ``JAX_COMPILATION_CACHE_DIR`` and nowhere else."""
    root, given = tmp_path / "checkout", tmp_path / "given"
    root.mkdir()
    extra = {"JAX_COMPILATION_CACHE_DIR": str(given)} if env_set else {}
    r = subprocess.run([sys.executable, "-c", _CACHE_PROG, str(root)],
                       env=_env(**extra), capture_output=True, text=True,
                       timeout=120, cwd=tmp_path)
    assert r.returncode == 0, r.stderr
    want = given if env_set else root / ".jax_cache"
    assert r.stdout.strip() == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert (root / ".jax_cache").exists() == (not env_set)
