"""Whole round step's share of the chip's bf16 peak: model FLOPs per token
(forward and backward, recompute not counted) × tokens per second of the
traced window, over chips × peak."""


def read(m):
    if m.kind != "round":
        return None
    rate = m.win["tokens"] / m.win["seconds"]
    return 100.0 * m.flops_per_token * rate / (m.chips * m.peak["bf16_flops"])
