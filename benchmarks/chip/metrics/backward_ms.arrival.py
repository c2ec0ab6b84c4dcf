"""Device milliseconds per arrival under the program's ``dude.backward``
scope (the arriving worker's forward and backward): the union of the
intervals of the ops counted under it (``scoped.attribute``) in the window
over the program's ``dude.arrival`` host spans there.  Nothing where the
program has no such scope or span."""

import scoped

SCOPES = ("dude.backward",)


def read(m):
    if m.kind != "arrival":
        return None
    trace = scoped.of(m)
    if trace is None:
        return None
    arrivals = trace.host_count(("dude.arrival",))
    t = trace.scope_time(SCOPES)
    if arrivals == 0 or t <= 0:
        return None
    return 1e3 * t / arrivals
