"""Mean time from a request's submit to its dispatch, on the engine's
clock (the engines' own ``stats.queued_s / stats.requests``), over the
window.  None where the engine keeps no such counter."""


def read(run):
    stats = run.window.engine_stats.values()
    if not stats or any("queued_s" not in s for s in stats):
        return None
    requests = sum(s["requests"] for s in stats)
    if not requests:
        return None
    return sum(s["queued_s"] for s in stats) / requests * 1e3
