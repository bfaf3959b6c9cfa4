"""Share of the traced window in which the device idles while the host
is inside a scheduler step (``serve.step``) and not waiting on a device
result (``serve.sync``): the idle the scheduler's own host work causes
(``bench.hostspans.reduce``)."""


def read(ctx):
    h = ctx.get("host_spans")
    if not h or not h["steps"]:
        return None
    return 100.0 * h["idle_host_s"] / h["window_s"]
