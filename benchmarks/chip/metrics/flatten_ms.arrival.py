"""Device milliseconds per arrival spent changing layout: the program's
``dude.unravel`` (the worker's snapshot -> model pytree) and ``dude.ravel``
(its gradient pytree -> flat ``[P]``) scopes, as the union of the
intervals of the ops counted under them (``scoped.attribute``) in the
window over the program's ``dude.arrival`` host spans there.  Nothing where
the program has no such scopes or span."""

import scoped

SCOPES = ("dude.unravel", "dude.ravel")


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
