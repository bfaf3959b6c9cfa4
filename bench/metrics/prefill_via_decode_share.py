"""Share of the prompt tokens prefilled in the window that the decode
program took (chunk-1 lanes folded into a decode step) rather than a
prefill-chunk program: the program's
``serve_prefill_tokens_total{path="decode"}`` over both paths, each
differenced between the window's ends.  Nothing to read where no prompt
token was prefilled in the window."""


def read(ctx):
    a, b = ctx["at_open"], ctx["at_close"]
    if "prefill_tokens" not in a:
        return None
    took = {p: b["prefill_tokens"][p] - a["prefill_tokens"][p] for p in ("decode", "chunk")}
    total = took["decode"] + took["chunk"]
    if total <= 0:
        return None
    return 100.0 * took["decode"] / total
