"""The held experts' grouped matmuls' share of their roofline: the least
time their work must take (``moe_counts.expert_work`` on the pairs the
program counted as routed to held experts in the window: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak) over the device time
of the ops under the program's ``dude.moe_experts`` scope (innermost,
``moe_scoped``) in the window.  Nothing where the program has no such
scope or count."""

import moe_counts
import moe_scoped


def read(m):
    if m.kind != "round" or not m.win.get("moe_rows_held"):
        return None
    trace = moe_scoped.of(m)
    if trace is None:
        return None
    t = trace.scope_time((moe_scoped.EXPERTS,))
    if t <= 0:
        return None
    # every round of the window runs inside the traced window
    flops, nbytes = moe_counts.expert_work(
        m.win["cfg"], m.win["moe_rows_held"], m.win["mix"]["n_workers"])
    least = max(flops / m.peak["bf16_flops"],
                nbytes / m.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
