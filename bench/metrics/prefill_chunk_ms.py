"""Mean device time of one execution of a prefill-chunk program (any
chunk size), from the device trace of the window."""


def read(ctx):
    p = ctx["trace"]["programs"].get("chunk")
    if not p or not p["n"]:
        return None
    return 1e3 * p["device_s"] / p["n"]
