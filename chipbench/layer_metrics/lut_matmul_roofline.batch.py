"""LUT GEMM's share of its roofline: the least time its calls need (the
larger of operations over the bf16 peak and bytes over HBM bandwidth, from
each call's shapes) over their device time in the trace. At decode shapes
(M = 8 rows) HBM bounds every call."""

from chipbench import costs, peaks, trace


def read(ctx):
    pk = peaks.peak(ctx.device_kind)
    least = spent = 0.0
    for e in ctx.trace.op_events:
        if "custom-call" not in e.long_name:
            continue
        cost = costs.lut_matmul(trace.shapes(e.long_name))
        if cost is None:
            continue
        least += costs.roofline_s(*cost, pk)[0]
        spent += e.dur_ns * 1e-9
    return least / spent * 100.0 if spent else None
