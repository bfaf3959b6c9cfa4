"""Share of its roofline that the paged-attention kernel reaches in the
decode program: the least time for the K/V rows the window's decode
steps read (the program's ``serve_attn_read_blocks`` count, differenced
over the window, times the block size, in every layer that attends, as
the reference's ``dims_of`` counts them) over the kernel's device time
in the trace."""
from bench import costs


def read(ctx):
    k = ctx["trace"]["kernels"].get(("decode", "paged_attention"))
    blocks = ctx["at_close"]["attn_blocks"] - ctx["at_open"]["attn_blocks"]
    if not k or not k["n"] or blocks <= 0:
        return None
    m = ctx["model"]
    flops, nbytes = costs.paged_attention(blocks * ctx["block_size"], m["n_heads"],
                                          m["n_kv_heads"], m["head_dim"])
    layers = ctx["dims"]["attn_layers"]
    least = costs.least_time(layers * flops, layers * nbytes, ctx["peak"])[0]
    return 100.0 * least / k["device_s"]
