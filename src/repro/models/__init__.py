from repro.models.model import (  # noqa: F401
    abstract_params,
    decode_fn,
    init_adapters,
    init_cache,
    init_params,
    loss_fn,
    merge_adapters,
    num_params,
    param_axes,
    predict_fn,
    prefill_fn,
)
from repro.models.transformer import ShardCtx, NULL_CTX  # noqa: F401
