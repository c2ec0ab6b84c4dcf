"""Whole arrival path's share of the chip's bf16 peak: model FLOPs per token
× tokens of the arrivals per second of the traced window (one worker's
batch per arrival), over chips × peak."""


def read(m):
    if m.kind != "arrival":
        return None
    rate = m.win["tokens"] / m.win["seconds"]
    return 100.0 * m.flops_per_token * rate / (m.chips * m.peak["bf16_flops"])
