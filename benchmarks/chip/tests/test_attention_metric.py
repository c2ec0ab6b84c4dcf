"""``metrics/attention_ms.round.py``: device time a round step under the
program's ``dude.attention`` scope, innermost, with the splash-attention
kernels (no op_name) put under it by name; nothing where no op carries the
scope (a program that predates it), and nothing on the arrival path.
"""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import harness  # noqa: E402
import moe_scoped  # noqa: E402
import run  # noqa: E402

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CELLS = ("qwen2-0.5b.round.n8", "qwen3-1.7b.round.n2",
         "olmoe-1b-7b.round.n4.s4096")
NAME = "attention_ms.round"
STEP = "jit(flat_train_step)"
# op names as the round step writes them (a CPU lowering of the program)
SCAN = f"{STEP}/dude.backward/vmap(jvp())/dude.attention/dot_general"
SCAN_BWD = (f"{STEP}/dude.backward/vmap(transpose(dude.backward))/"
            "vmap(jvp())/checkpoint/dude.attention/exp")
PROJ = f"{STEP}/dude.backward/vmap(jvp())/dot_general"
ROUND = f"{STEP}/dude.round/x"
# the kernels' events as a TPU profile names them (no op_name)
FWD = ("%splash_mha_fwd_residuals.14 = (bf16[4,16,4096,128]{3,2,1,0}, "
       "f32[4,16,4096,128]{3,2,1,0}) custom-call(%a, %b), "
       'custom_call_target="tpu_custom_call"')
DKV = ("%splash_mha_dkv_no_residuals.3 = (bf16[4,16,4096,128]{3,2,1,0}) "
       'custom-call(%a), custom_call_target="tpu_custom_call"')
DQ = ("%splash_mha_dq_no_residuals.7 = bf16[4,16,4096,128]{3,2,1,0} "
      'custom-call(%a), custom_call_target="tpu_custom_call"')


def _input(cell, ops, window, steps=1, kind="round"):
    """A MetricInput over one chip's synthetic ``[name, start, dur,
    op_name]`` op list, scoped as the innermost reader scopes them."""
    def scope(name):
        hit = moe_scoped.INNERMOST.search(name)
        return hit.group(1) if hit else ""
    step = window / steps
    trace = {"chips": [{"ops": [r[:3] for r in ops],
                        "op_scopes": [scope(r[3]) for r in ops],
                        "modules": [["jit_flat_train_step(1)", i * step,
                                     step] for i in range(steps)]}],
             "host": [["window", 0, window]], "host_ids": [{}]}
    cell_ = run.find(BENCH, "workloads", cell)
    cfg = harness.load_json(HERE / "configs" / f"{cell_['config']}.json")
    mix = harness.load_json(HERE / "traffic" / f"{cell_['traffic']}.json")
    win = {"tokens": mix["n_workers"] * mix["seq_len"], "seconds": 20.0,
           "moe_rows_held": 0, "cfg": cfg, "mix": mix}
    return run.MetricInput(kind, moe_scoped.inner.Scoped(trace), win, cfg,
                           mix, 1, counts.peaks("TPU v5 lite"))


def _read(cell, m):
    got = run.per_layer(BENCH, cell, m)
    return got[NAME]["value"] if NAME in got else None


def test_listed_for_the_round_cells_only():
    entry = run.find(BENCH, "per_layer", NAME)
    assert tuple(entry["workloads"]) == CELLS
    assert entry["moves"] == "tokens_per_s"
    assert entry["layer"] == "attention"


@pytest.mark.parametrize("cell", CELLS)
def test_kernel_path_counts_the_kernels(cell):
    """Two steps: the kernels count under the scope, and so does the copy
    just before one (an op with no op_name counts under the next op);
    the projections and the round do not."""
    one = [["proj", 0, 10, PROJ],
           ["copy", 10, 5, ""],
           [FWD, 15, 40, ""],
           ["proj", 55, 10, PROJ],
           [DKV, 65, 30, ""],
           [DQ, 95, 20, ""],
           ["transpose", 115, 5, SCAN_BWD],
           ["round", 120, 80, ROUND]]
    ops = one + [[n, s + 200, d, o] for n, s, d, o in one]
    got = _read(cell, _input(cell, ops, 400, steps=2))
    assert got == pytest.approx(1e-6 * (5 + 40 + 30 + 20 + 5))


@pytest.mark.parametrize("cell", CELLS)
def test_scan_path_counts_the_scoped_ops(cell):
    ops = [["proj", 0, 10, PROJ], ["dot", 10, 30, SCAN],
           ["exp", 40, 20, SCAN_BWD], ["round", 60, 40, ROUND]]
    assert _read(cell, _input(cell, ops, 100)) == pytest.approx(50e-6)


@pytest.mark.parametrize("cell", CELLS)
def test_no_scope_reads_nothing(cell):
    """A program that predates the scope (its kernels never named): the
    metric is left out and nothing raises."""
    ops = [["proj", 0, 10, PROJ], ["dot", 10, 30, PROJ],
           ["round", 40, 60, ROUND]]
    assert _read(cell, _input(cell, ops, 100)) is None


def test_arrival_path_reads_nothing():
    mod = harness.load_module(HERE / "metrics" / f"{NAME}.py", "attn_metric")
    ops = [["dot", 0, 30, SCAN], [FWD, 30, 10, ""]]
    assert mod.read(_input(CELLS[0], ops, 40, kind="arrival")) is None


def test_kernel_names_match_the_pattern():
    mod = harness.load_module(HERE / "metrics" / f"{NAME}.py", "attn_metric")
    for name in (FWD, DKV, DQ):
        assert mod.KERNEL.match(name)
    for name in ("%fusion.3 = bf16[8]{0} fusion()",
                 '%dude.round.1 = (f32[8]) custom-call(%g_workers)',
                 "%ragged-dot-none.6 = bf16[8]{0} custom-call(%x)"):
        assert not mod.KERNEL.match(name)
