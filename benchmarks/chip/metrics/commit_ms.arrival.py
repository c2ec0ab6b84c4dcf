"""Device milliseconds per arrival of the jitted server iteration (commit
into ``g_bar`` and the optimizer apply), summed from the events of its XLA
module in the trace over the arrivals they ran for."""

MODULE = "_arrival_step"


def read(m):
    if m.kind != "arrival":
        return None
    hit = lambda name: MODULE in name  # noqa: E731
    calls = m.trace.count("modules", hit)
    if calls == 0:
        return None
    return 1e3 * m.trace.module_time(hit) / calls
