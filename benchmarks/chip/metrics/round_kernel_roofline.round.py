"""Fused DuDe round kernel's share of its roofline: the least time the
bytes of the round must take at the HBM peak (``counts.round_bytes``, per
kernel call), over the device time of the kernel's events in the trace,
found as the step's Pallas custom call that takes the ``g_workers`` slab.
It is bound by bandwidth: the round does under one FLOP per byte.  Nothing
where no such kernel ran."""

KERNEL = 'custom_call_target="tpu_custom_call"'
OPERAND = "g_workers"


def read(m):
    if m.kind != "round":
        return None
    hit = lambda name: KERNEL in name and OPERAND in name  # noqa: E731
    t = m.trace.op_time(hit)
    calls = m.trace.count("ops", hit)
    if t <= 0 or calls == 0:
        return None
    return 100.0 * calls * m.round_bytes / m.peak["hbm_bytes_per_s"] / t
