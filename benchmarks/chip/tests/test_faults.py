"""A whole run of each cell at a small size on the CPU, past the harness's
look for a chip: sound, it comes out ``correct``; with the timed path
broken underneath, it does not.  Also the control: the plain reference
one precision step down (float8) fails the cell's limits.

Faults planted, for each cell (both kinds of cell train):

- ``frozen``: the step returns its state unchanged;
- ``half``: half of every worker's batch is left out (labels masked), the
  mean taken over the rest.

A cell on several chips runs in a child process on as many virtual CPU
devices.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import run as bench_run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
# the four-chip mix has no cell yet (not proven on chips): its mesh path
# runs here under the limits of the one-chip cell of the same config
MESH = {"name": "qwen2-0.5b.round.n16.x4", "config": "qwen2-0.5b",
        "traffic": "round.n16.x4", "chips": 4}
MESH_LIMITS = "qwen2-0.5b.round.n8"
if MESH["name"] not in CELLS:
    BENCH["workloads"].append(MESH)
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 12345     # more than 32 signed bits hold


def small(workload: str):
    """The cell's configuration and mix, at every size cut down."""
    cell = bench_run.find(BENCH, "workloads", workload)
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=128, vocab_size=512,
               num_hidden_layers=2)
    cfg["run"] = dict(cfg["run"], ce_chunk=16)
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    mix.update(seq_len=32, pool=4)
    if mix["mode"] == "arrival":
        mix.update(warm_arrivals=12, record_every=5)
    return cfg, mix


def frozen(run, mode):
    """Plant a step that returns its state unchanged."""
    import jax
    from repro.api import Trainer
    from repro.optim import FlatTrainState
    from repro.runtime.runner import AsyncRunner

    def step(self, batch, sm, cm):
        _, metrics = jax.jit(self.step_fn)(self.state, batch, sm, cm)
        self.rounds += 1
        return metrics

    def arrival_step(self, params, opt, srv, worker, grad, tau):
        return FlatTrainState(params, opt, srv)

    return {(Trainer, "step"): step,
            (AsyncRunner, "_arrival_step"): arrival_step}


def half(run, mode):
    """Plant a feed that leaves half of every batch out."""
    import jax

    def feed(b):
        labels = np.array(b["labels"])
        labels[..., labels.shape[-1] // 2:] = -1
        return jax.device_put(dict(b, labels=labels))

    mode.feed = feed
    return {}


FAULTS = {"frozen": frozen, "half": half}


def run_small(workload, monkeypatch=None, fault=None):
    """One run of ``workload`` at the small size, with ``fault`` (a name of
    ``FAULTS``) planted; in a child process when the cell needs more
    devices than this one has."""
    import jax
    chips = bench_run.find(BENCH, "workloads", workload)["chips"]
    if jax.device_count() < chips:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={chips}"))
        out = subprocess.run(
            [sys.executable, __file__, workload, fault or ""], env=env,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])
    cfg, mix = small(workload)

    def hook(run, mode):
        if fault:
            for (cls, name), fn in FAULTS[fault](run, mode).items():
                monkeypatch.setattr(cls, name, fn)

    limits = None
    if workload == MESH["name"]:
        limits = json.loads((HERE / "limits" / f"{MESH_LIMITS}.json")
                            .read_text())
    return bench_run.run_cell(BENCH, workload, SEED, 0.5, False, chips=chips,
                              peak=PEAK, cfg=cfg, mix=mix, hook=hook,
                              limits=limits)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = run_small(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    res = run_small(workload, monkeypatch, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [None, "frozen"], ids=["sound", "frozen"])
def test_mesh_path_on_four_devices(fault, monkeypatch):
    """The round mode on a 1x4 mesh (P-sharded engine, TP params feed)."""
    res = run_small(MESH["name"], monkeypatch, fault)
    assert res["device"]["count"] == 4
    assert res["correct"] == (fault is None), res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload):
    """The reference in float8 in the program's place is not correct."""
    cfg, mix = small(workload)
    _, run, mode = bench_run.make_run(BENCH, workload, SEED, 0.5, False,
                                      cfg, mix)
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    g = check.gaps(mode.reference("fp8"), mode.reference("f32"))
    ok, rows = check.decide(g, limits)
    assert not ok, rows


if __name__ == "__main__":
    # child of run_small: one run on this process's devices
    with pytest.MonkeyPatch.context() as mp:
        print(json.dumps(run_small(sys.argv[1], mp, sys.argv[2] or None)))
