"""Host time the engines spend blocked on the device per retired
dispatch (their own ``stats.wait_s / stats.retired``, the
``serve.wait`` span), over the window.  None where the engine keeps no
such counter."""


def read(run):
    stats = run.window.engine_stats.values()
    if not stats or any("wait_s" not in s for s in stats):
        return None
    retired = sum(s["retired"] for s in stats)
    if not retired:
        return None
    return sum(s["wait_s"] for s in stats) / retired * 1e3
