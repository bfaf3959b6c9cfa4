"""Mean share of the lanes that each decode step in the window computes
for a live request (the program's ``serve_occupancy`` histogram, count
and sum differenced between the window's ends)."""


def read(ctx):
    a, b = ctx["at_open"], ctx["at_close"]
    steps = b["occ_count"] - a["occ_count"]
    if steps <= 0:
        return None
    return 100.0 * (b["occ_sum"] - a["occ_sum"]) / steps / ctx["n_slots"]
