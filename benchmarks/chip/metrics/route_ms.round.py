"""Device milliseconds per round step under the program's
``dude.moe_route`` scope alone (router, top-k, sort, dispatch gathers,
combine), each op under its innermost scope (``moe_scoped``).  Nothing
where the program has no such scope."""

import moe_scoped


def read(m):
    return moe_scoped.per_round_ms(m, (moe_scoped.ROUTE,))
