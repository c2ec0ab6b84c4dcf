"""Whole MoE round step's share of the chip's bf16 peak: model FLOPs of the
traced window (``moe_counts.train_flops``: every token's attention, router
and head, and the held experts' work on the pairs the program counted as
routed to them, forward and backward, recompute not counted) per second of
the window, over chips × peak.  Nothing where the window has no count of
routed pairs (a program without the MoE counters)."""

import moe_counts


def read(m):
    if m.kind != "round" or not m.win.get("moe_rows_held"):
        return None
    flops = moe_counts.train_flops(m.win["cfg"], m.win["mix"]["seq_len"],
                                   m.win["tokens"], m.win["moe_rows_held"])
    return 100.0 * flops / m.win["seconds"] / (m.chips
                                                * m.peak["bf16_flops"])
