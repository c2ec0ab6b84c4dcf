"""Device milliseconds per round step under the program's ``dude.backward``
scope (the n workers' forward and backward, vmapped): the union of the
intervals of the ops counted under it (``scoped.attribute``) in the window
over the round step's module events there.  Nothing where the program tags
no op with the scope."""

import scoped

MODULE = "jit_flat_train_step("
SCOPES = ("dude.backward",)


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
