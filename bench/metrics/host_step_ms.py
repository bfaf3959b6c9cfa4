"""Host time of one scheduler step not spent waiting on the device: the
program's ``serve_step_ms`` histogram (sum over count) less its
``serve_host_seconds_total{phase="sync"}``, both differenced between the
window's ends (``bench.hostspans.counters``)."""


def read(ctx):
    a, b = ctx["at_open"], ctx["at_close"]
    if "step_count" not in a:
        return None
    steps = b["step_count"] - a["step_count"]
    if steps <= 0:
        return None
    sync_s = b["host_s"]["sync"] - a["host_s"]["sync"]
    return (b["step_ms_sum"] - a["step_ms_sum"] - 1e3 * sync_s) / steps
