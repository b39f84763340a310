"""95th-percentile latency from due time to logits on the host, over
every request due in the window."""
import measures


def read(run):
    if not run.window.open_loop:
        return None
    return measures.percentile(
        measures.latencies_ms(run.window.recs, run.gave_up), 95)
