"""Device milliseconds per round step in the MoE layers: the ops counted
under the program's ``dude.moe_route`` (router, top-k, sort, dispatch
gathers, combine) and ``dude.moe_experts`` (grouped matmuls) scopes, each
op under its innermost scope (``moe_scoped``), as the union of their
intervals in the window over the round step's module events there.
Nothing where the program has no such scopes."""

import moe_scoped


def read(m):
    return moe_scoped.per_round_ms(m, (moe_scoped.ROUTE, moe_scoped.EXPERTS))
