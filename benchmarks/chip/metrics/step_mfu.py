"""The whole serving step's share of the chip's peak: operations of the
real clouds dispatched in the traced window (counted from the
configuration's shapes) over the device's busy time in that window
(every program it ran, whatever its name) times the peak at the
configuration's precision, in percent."""
import work


def read(run):
    red = run.trace
    if red is None or not run.trace_counts:
        return None
    flops, capacity = 0.0, 0.0
    for dev, tenant in run.device_tenant.items():
        c = run.configs[tenant]
        capacity += red.busy_s.get(dev, 0.0) * work.compute_peak(
            run.device_kind, c["precision"])
    for tenant, (requests, _) in run.trace_counts["end"].items():
        c = run.configs[tenant]
        n = requests - run.trace_counts["start"][tenant][0]
        st0, st1 = run.trace_counts["stream_start"], \
            run.trace_counts["stream_end"]
        hits = st1["hits"] - st0["hits"] if st1["frames"] else 0
        flops += (n - hits) * work.cloud_flops(c) \
            + hits * work.cloud_flops(c, replayed=True)
    if capacity <= 0 or flops <= 0:
        return None
    return 100.0 * flops / capacity
