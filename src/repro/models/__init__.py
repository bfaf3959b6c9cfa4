"""Model substrate: layers, architectures, frontends."""
from . import attention, common, frontends, moe, resnet, rglru, ssm, transformer  # noqa: F401
from .transformer import (  # noqa: F401
    decode_step,
    forward,
    init_cache,
    init_packed_params,
    init_params,
    loss_fn,
    param_count,
    prefill,
    prefill_chunk,
)
