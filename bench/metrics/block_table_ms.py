"""Host time per scheduler step in block grants and the eager
block-table update: the program's
``serve_host_seconds_total{phase="blocks"}`` over its ``serve_step_ms``
count, both differenced between the window's ends
(``bench.hostspans.counters``)."""


def read(ctx):
    a, b = ctx["at_open"], ctx["at_close"]
    if "step_count" not in a:
        return None
    steps = b["step_count"] - a["step_count"]
    if steps <= 0:
        return None
    return 1e3 * (b["host_s"]["blocks"] - a["host_s"]["blocks"]) / steps
