"""Model FLOPs of every token the window prefilled or generated, over the
window's length times the chip's bf16 peak.

A prefill chunk event of ``n`` rows counts the prompt positions it
filled; a decode step counts the position of the token it fed.  Each
position costs two FLOPs per matmul weight (the head included) plus
attention over the positions it sees, in the layers that attend (the
configuration's reference module's ``model_flops``)."""


def positions(events, prompt_len, t_open, t_close):
    out = []
    for uid, evs in events.items():
        filled = decoded = 0
        for kind, ts, attrs in evs:
            inside = t_open <= ts < t_close
            if kind == "prefill_chunk":
                n = int(attrs.get("size", 0))
                if inside:
                    out.extend(range(filled, filled + n))
                filled += n
            elif kind == "decode_step":
                decoded += 1
                if inside:
                    out.append(prompt_len[uid] + decoded - 1)
    return out


def read(ctx):
    pos = positions(ctx["events"], ctx["prompt_len"], ctx["t_open"], ctx["t_close"])
    if not pos:
        return None
    flops = ctx["reference"].model_flops(ctx["model"], ctx["dims"], pos)
    return 100.0 * flops / ((ctx["t_close"] - ctx["t_open"]) * ctx["peak"]["bf16_flops"])
