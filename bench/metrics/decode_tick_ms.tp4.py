"""decode_tick_ms.tp4: Runner: host time per decode chunk, in the window.
Moves itl_p95_ms."""
from bench.readers import decode_tick_ms as read  # noqa: F401
