"""Host time the engines spend stacking and padding per dispatch
(their own ``stats.host_s / stats.batches``), over the window."""


def read(run):
    stats = run.window.engine_stats.values()
    batches = sum(s["batches"] for s in stats)
    if not batches:
        return None
    return sum(s["host_s"] for s in stats) / batches * 1e3
