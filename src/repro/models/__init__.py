from repro.models import layers, model, params, ssd  # noqa: F401
from repro.models.model import (  # noqa: F401
    decode_step,
    init,
    init_paged_cache,
    loss_fn,
    paged_cache_specs,
    param_shapes,
    param_specs,
    prefill,
    write_prefill,
)
