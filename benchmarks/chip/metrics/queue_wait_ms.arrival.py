"""Host milliseconds per arrival blocked on the program's device queue: the
self time of its ``dude.queue_wait`` spans in the window over its
``dude.arrival`` spans there.  While the host waits there the device runs
the queued steps, so this is the host's slack, not the device's (a jit
dispatch that waits for device memory shows under ``dude.grad`` or
``dude.commit`` instead).  0 when the program's loop never blocked;
nothing where it has no ``dude.arrival`` span."""

import scoped


def read(m):
    if m.kind != "arrival":
        return None
    trace = scoped.of(m)
    if trace is None:
        return None
    arrivals = trace.host_count(("dude.arrival",))
    if arrivals == 0:
        return None
    return 1e3 * trace.host_time(("dude.queue_wait",)) / arrivals
