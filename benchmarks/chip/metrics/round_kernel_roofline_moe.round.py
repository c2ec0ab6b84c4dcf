"""The fused DuDe round kernel's share of its roofline in an MoE round
cell: ``round_kernel_roofline.round`` (loaded as it is: the same kernel
events, the same ``counts.round_bytes``), with P the length of the
program's own flat parameter state (the window's ``param_count``) instead
of ``counts.param_count``, which counts a dense layer.  Nothing where the
window has no such count (a program without the MoE mode's reading) or no
such kernel ran."""

from pathlib import Path

import counts
import harness

_dense = harness.load_module(
    Path(__file__).resolve().parent / "round_kernel_roofline.round.py",
    "metric_round_kernel_roofline_dense")


def read(m):
    P = m.win.get("param_count")
    if m.kind != "round" or not P:
        return None
    hit = lambda name: _dense.KERNEL in name and _dense.OPERAND in name  # noqa: E731
    t = m.trace.op_time(hit)
    calls = m.trace.count("ops", hit)
    if t <= 0 or calls == 0:
        return None
    run = m.win["cfg"]["run"]
    nbytes = counts.round_bytes(m.win["mix"]["n_workers"], P,
                                counts.dtype_bytes(run["grad_dtype"]),
                                counts.dtype_bytes(run["buffer_dtype"]))
    return 100.0 * calls * nbytes / m.peak["hbm_bytes_per_s"] / t
