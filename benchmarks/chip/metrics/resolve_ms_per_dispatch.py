"""Host time the engines spend resolving a retired dispatch: splitting
it into rows, resolving the futures and running their done-callbacks
(their own ``stats.resolve_s / stats.retired``, the ``serve.resolve``
span), over the window.  None where the engine keeps no such counter."""


def read(run):
    stats = run.window.engine_stats.values()
    if not stats or any("resolve_s" not in s for s in stats):
        return None
    retired = sum(s["retired"] for s in stats)
    if not retired:
        return None
    return sum(s["resolve_s"] for s in stats) / retired * 1e3
