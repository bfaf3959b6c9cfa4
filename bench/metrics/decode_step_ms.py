"""Mean device time of one execution of the decode program, from the
device trace of the window."""


def read(ctx):
    p = ctx["trace"]["programs"].get("decode")
    if not p or not p["n"]:
        return None
    return 1e3 * p["device_s"] / p["n"]
