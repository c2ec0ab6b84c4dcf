"""The MoE cell's counts and metrics (``moe_counts.py``, ``moe_scoped.py``,
``metrics/{mfu_moe,moe_ms,route_ms,expert_roofline,
round_kernel_roofline_moe}.round.py``): the counts against a hand count of
OLMoE's shapes, the innermost-scope reader on op names as the program
writes them and on a synthetic op list, and the grouped matmuls' and the
round kernel's roofline shares never above 100 %.
"""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import harness  # noqa: E402
import moe_counts  # noqa: E402
import moe_scoped  # noqa: E402
import run  # noqa: E402
import scoped  # noqa: E402

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CELL = "olmoe-1b-7b.round.n4.s4096"
CFG = harness.load_json(HERE / "configs" / "olmoe-1b-7b.json")
MIX = harness.load_json(HERE / "traffic" / "round.n4.s4096.json")
PEAK = counts.peaks("TPU v5 lite")
# op names as the round step writes them (a CPU lowering of the program)
FWD = "jit(flat_train_step)/dude.backward/vmap(jvp())/dude.moe_experts/ragged_dot_general"
BWD = ("jit(flat_train_step)/dude.backward/vmap(transpose(dude.backward))/"
       "vmap(jvp())/dude.moe_route/gather")
ATTN = "jit(flat_train_step)/dude.backward/vmap(jvp())/dot_general"
# a grouped-matmul kernel's event as the TPU profile names it (no op_name)
GMM = ('%ragged-dot-none.6 = bf16[131072,1024]{1,0} custom-call(%x, %w, '
       '%g), custom_call_target="tpu_custom_call"')


def test_counts_follow_the_shapes():
    d, H, hd, f, E, V, L = 2048, 16, 128, 1024, 64, 6288, 4
    S = MIX["seq_len"]
    weights = L * (4 * d * H * hd + d * E) + d * V
    attn = L * 2 * H * hd * S
    assert moe_counts.dense_flops_per_token(CFG, S) == 3 * (2 * weights
                                                            + attn)
    assert moe_counts.expert_flops_per_row(CFG) == 18 * d * f
    tokens, rows = 4 * S, 4 * S * L
    assert moe_counts.train_flops(CFG, S, tokens, rows) == pytest.approx(
        tokens * moe_counts.dense_flops_per_token(CFG, S)
        + rows * 18 * d * f)
    flops, nbytes = moe_counts.expert_work(CFG, rows, 4)
    assert flops == 11 * 2 * d * f * rows
    w = 8 * d * f * 2
    assert nbytes == 11 * (d + f) * 2 * rows + L * (11 + 3 * 4) * w


def test_innermost_scope_of_an_op_name():
    """The accepted reader takes the layer scope; the MoE reader the
    innermost; a name with one scope reads the same in both."""
    assert scoped._SCOPE.search(FWD).group(1) == "dude.backward"
    assert moe_scoped.INNERMOST.search(FWD).group(1) == "dude.moe_experts"
    assert moe_scoped.INNERMOST.search(BWD).group(1) == "dude.moe_route"
    assert moe_scoped.INNERMOST.search(ATTN).group(1) == "dude.backward"
    assert moe_scoped.INNERMOST.search("fusion.3") is None
    # the copy in use is scoped.py's own code with the pattern swapped
    assert moe_scoped.inner._SCOPE is moe_scoped.INNERMOST
    assert moe_scoped.inner is not scoped
    assert moe_scoped._extract.__code__.co_code == \
        scoped.extract.__code__.co_code
    assert moe_scoped.KERNEL.match(GMM)
    assert not moe_scoped.KERNEL.match("%fusion.3 = bf16[8]{0} fusion()")


# the fused round kernel's event (its operand names the g_workers slab)
ROUND = ('%dude.round.1 = (f32[8]) custom-call(%g_workers, %fresh), '
         'custom_call_target="tpu_custom_call"')
P_PROGRAM = 294_750_208


def _input(ops, window, rows, seconds=20.0, tokens=None, param_count=None):
    """A MetricInput over one chip's synthetic ``[name, start, dur,
    op_name]`` op list, scoped as the innermost reader scopes them."""
    def scope(name):
        hit = moe_scoped.INNERMOST.search(name)
        return hit.group(1) if hit else ""
    trace = {"chips": [{"ops": [r[:3] for r in ops],
                        "op_scopes": [scope(r[3]) for r in ops],
                        "modules": [["jit_flat_train_step(1)", 0, window]]}],
             "host": [["window", 0, window]], "host_ids": [{}]}
    win = {"tokens": tokens or 4 * MIX["seq_len"], "seconds": seconds,
           "moe_rows_held": rows, "cfg": CFG, "mix": MIX}
    if param_count:
        win["param_count"] = param_count
    trace = moe_scoped.mark_kernels(trace)
    return run.MetricInput("round", moe_scoped.inner.Scoped(trace), win,
                           CFG, MIX, 1, PEAK)


def test_moe_metrics_read_a_synthetic_step():
    ops = [["attn", 0, 100, ATTN],
           ["route", 100, 30, BWD],
           ["copy", 130, 10, ""],              # counts under the next op
           ["silu", 140, 60, FWD],
           [GMM, 200, 20, ""],                 # the kernel: no op_name
           ["combine", 220, 10, BWD],
           ["round", 230, 50, "jit(flat_train_step)/dude.round/x"]]
    m = _input(ops, 300, rows=16384)
    got = {k: v["value"] for k, v in run.per_layer(BENCH, CELL, m).items()}
    assert got["route_ms.round"] == pytest.approx(40e-6)
    assert got["moe_ms.round"] == pytest.approx(130e-6)
    assert got["mfu_moe.round"] == pytest.approx(
        100 * moe_counts.train_flops(CFG, MIX["seq_len"], 4 * MIX["seq_len"],
                                     16384) / 20.0 / PEAK["bf16_flops"])
    assert got["expert_roofline.round"] > 0


@pytest.mark.parametrize("rows", [1, 2048, 16384, 131072])
def test_expert_roofline_is_at_most_100(rows):
    """The experts' time is never below the least time their counted work
    takes: at that time the share reads 100 %, and above it less."""
    flops, nbytes = moe_counts.expert_work(CFG, rows, MIX["n_workers"])
    least = max(flops / PEAK["bf16_flops"], nbytes / PEAK["hbm_bytes_per_s"])
    ns = least * 1e9
    for t, want in ((ns, 100.0), (2 * ns, 50.0)):
        m = _input([[GMM, 0, t, ""]], t, rows)
        got = run.per_layer(BENCH, CELL, m)["expert_roofline.round"]["value"]
        assert got == pytest.approx(want, rel=1e-6)
        assert got <= 100.0 + 1e-9


def test_no_counter_reads_no_moe_metric():
    """A program without the MoE counters or scopes (the parent of the
    change that added them): the MoE metrics are left out, nothing
    raises."""
    m = _input([["attn", 0, 100, ATTN]], 100, rows=0)
    got = run.per_layer(BENCH, CELL, m)
    assert not {"mfu_moe.round", "expert_roofline.round", "moe_ms.round",
                "route_ms.round", "round_kernel_roofline_moe.round"} & set(got)


def _round_bytes(P):
    run_ = CFG["run"]
    return counts.round_bytes(MIX["n_workers"], P,
                              counts.dtype_bytes(run_["grad_dtype"]),
                              counts.dtype_bytes(run_["buffer_dtype"]))


@pytest.mark.parametrize("calls", [1, 3])
def test_round_kernel_roofline_counts_the_programs_params(calls):
    """The round kernel's share takes P from the program's flat state, not
    from the dense count of the configuration; at the least time the bytes
    take it reads 100 %, at twice that 50 %."""
    assert counts.param_count(CFG) != P_PROGRAM
    least_ns = _round_bytes(P_PROGRAM) / PEAK["hbm_bytes_per_s"] * 1e9
    for per_call, want in ((least_ns, 100.0), (2 * least_ns, 50.0)):
        ops = [[ROUND, i * per_call, per_call, ""] for i in range(calls)]
        m = _input(ops, calls * per_call, rows=16384, param_count=P_PROGRAM)
        got = run.per_layer(BENCH, CELL, m)
        assert got["round_kernel_roofline_moe.round"]["value"] == \
            pytest.approx(want, rel=1e-6)
    # the accepted metric's file is not listed for this cell
    assert "round_kernel_roofline.round" not in got
    # without the program's count the share is left out
    m = _input([[ROUND, 0, least_ns, ""]], least_ns, rows=16384)
    assert "round_kernel_roofline_moe.round" not in run.per_layer(BENCH, CELL,
                                                                  m)


def test_chip_cut_counts_the_kernels_under_the_experts():
    """A cut of a traced run on one v5e (the first 6000 ops of the window,
    most of one round), scoped by the innermost reader: every grouped-matmul
    kernel counts under ``dude.moe_experts``, the two MoE scopes split the
    MoE time and lie inside the round's backward."""
    import gzip
    path = HERE / "tests" / "data" / f"scoped_inner.{CELL}.json.gz"
    with gzip.open(path, "rt") as f:
        compact = json.load(f)
    chip = compact["chips"][0]
    kernels = [sc for r, sc in zip(chip["ops"], chip["op_scopes"])
               if moe_scoped.KERNEL.match(r[0])]
    assert kernels and set(kernels) == {moe_scoped.EXPERTS}
    red = moe_scoped.inner.Scoped(compact)
    route = red.scope_time((moe_scoped.ROUTE,))
    experts = red.scope_time((moe_scoped.EXPERTS,))
    both = red.scope_time((moe_scoped.ROUTE, moe_scoped.EXPERTS))
    assert route > 0 and experts > 0
    assert both == pytest.approx(route + experts, rel=1e-6)
    assert both < red.scope_time(("dude.backward",))
    m = run.MetricInput("round", red, {"tokens": 4 * MIX["seq_len"],
                                       "seconds": red.window_s,
                                       "moe_rows_held": 0, "cfg": CFG,
                                       "mix": MIX}, CFG, MIX, 1, PEAK)
    got = run.per_layer(BENCH, CELL, m)
    assert got["moe_ms.round"]["value"] == pytest.approx(1e3 * both)
    assert got["route_ms.round"]["value"] == pytest.approx(1e3 * route)
