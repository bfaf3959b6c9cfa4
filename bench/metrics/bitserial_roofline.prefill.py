"""Share of its roofline that the bitserial matmul kernel reaches inside
the prefill-chunk programs (every chunk size): the least time the chip
needs for the window's calls (the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, each packed weight counted once per call, shapes read from
each call's instruction in the trace) over the calls' device time."""
from bench import costs, devtrace

PROGRAM = "chunk"


def read(ctx):
    k = ctx["trace"]["kernels"].get((PROGRAM, "bitserial"))
    if not k or not k["n"]:
        return None
    least = 0.0
    for name, count in k["calls"].items():
        m, kk, n, n_bits = devtrace.bitserial_shape(name)
        least += count * costs.least_time(*costs.bitserial(m, kk, n, n_bits), ctx["peak"])[0]
    return 100.0 * least / k["device_s"]
