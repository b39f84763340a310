"""The CBR kernels' share of their roofline: for every lane the serving
programs ran in the traced window, the least time its CBR layers could
take on this chip (per layer the larger of operations over peak and
bytes over HBM bandwidth, from the configuration's shapes), over the
device time of the CBR kernel events, in percent."""
import trace_reduce
import work

#: Base names of the serving programs and of the CBR Pallas kernels.
PROGRAMS = ("jit_fwd",)
KERNELS = ("fused_linear_pallas", "int8_matmul_pallas")


def read(run):
    red = run.trace
    if red is None:
        return None
    bound, spent = 0.0, 0.0
    for dev, tenant in run.device_tenant.items():
        c = run.configs[tenant]
        n, _ = trace_reduce.program_time(red, PROGRAMS, dev)
        if not n:
            continue
        bound += n * run.lanes_per_device[tenant] \
            * work.cbr_bound_s(c, run.device_kind)
        spent += sum(s for k, s in red.kernels_s.get(dev, {}).items()
                     if k in KERNELS)
    if spent <= 0 or bound <= 0:
        return None
    return 100.0 * bound / spent
