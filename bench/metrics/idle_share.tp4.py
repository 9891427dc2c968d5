"""idle_share.tp4: Device: share of the traced interval with no operation on
the device, averaged over the cell's chips. Moves ttft_p90_s."""
from bench.readers import idle_share as read  # noqa: F401
