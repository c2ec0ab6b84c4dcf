"""Host milliseconds per record point of the program's arrival loop: the
self time of its ``dude.record`` spans in the window over their count.  A
record point reads the loss back, so it waits for every step in flight:
the long period that sets ``arrival_ms_p95``.  Nothing where the program
has no such span."""

import scoped


def read(m):
    if m.kind != "arrival":
        return None
    trace = scoped.of(m)
    if trace is None:
        return None
    records = trace.host_count(("dude.record",))
    if records == 0:
        return None
    return 1e3 * trace.host_time(("dude.record",)) / records
