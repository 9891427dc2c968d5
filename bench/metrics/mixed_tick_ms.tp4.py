"""mixed_tick_ms.tp4: Runner: host time per mixed step (dispatch to sync, kept
by the engine), in the window. Moves ttft_p90_s."""
from bench.readers import mixed_tick_ms as read  # noqa: F401
