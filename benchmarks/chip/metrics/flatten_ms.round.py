"""Device milliseconds per round step spent changing layout: the program's
``dude.unravel`` (flat ``[P]`` params -> model pytree) and ``dude.ravel``
(the n gradient pytrees -> the ``[n, P]`` slab) scopes, as the union of
the intervals of the ops counted under them (``scoped.attribute``) in the
window over the round step's module events there.  Nothing where the
program tags no op with either scope."""

import scoped

MODULE = "jit_flat_train_step("
SCOPES = ("dude.unravel", "dude.ravel")


def read(m):
    if m.kind != "round":
        return None
    trace = scoped.of(m)
    if trace is None:
        return None
    steps = trace.count("modules", lambda name: name.startswith(MODULE))
    t = trace.scope_time(SCOPES)
    if steps == 0 or t <= 0:
        return None
    return 1e3 * t / steps
