"""Device milliseconds per round step in the attention core: the ops
counted under the program's ``dude.attention`` scope, each op under its
innermost scope (``moe_scoped``), as the union of their intervals in the
window over the round step's module events there (``moe_scoped.
per_round_ms``).  The Pallas splash-attention kernels' events
(``%splash_mha_fwd_residuals.N = ...``, ``..._dq_...``, ``..._dkv_...``)
carry no op_name, so the scope of the next op in time would take them;
they are put under ``dude.attention`` by name before the ops are
attributed.  Nothing where the program has no such scope."""

import copy
import re

import moe_scoped

SCOPE = "dude.attention"
KERNEL = re.compile(r"^%splash_m[hq]a_[\w.-]* = ")


def mark_kernels(compact: dict) -> dict:
    """A copy of a scoped compact trace with the splash kernels' rows
    under ``dude.attention``."""
    chips = [dict(c, op_scopes=[SCOPE if KERNEL.match(r[0]) else sc
                                for r, sc in zip(
                                    c["ops"], moe_scoped.inner.own_scopes(c))])
             for c in compact["chips"]]
    return dict(compact, chips=chips)


def read(m):
    trace = moe_scoped.of(m) if m.kind == "round" else None
    if trace is None:
        return None
    marked = copy.copy(m)
    marked.trace = moe_scoped.inner.Scoped(mark_kernels(trace.compact))
    return moe_scoped.per_round_ms(marked, (SCOPE,))
