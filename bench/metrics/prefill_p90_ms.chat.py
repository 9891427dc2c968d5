"""prefill_p90_ms.chat: Runner: 90th percentile of first-token time minus
admission (the start of the step that computed the request's first prompt
chunk), in the window. Moves ttft_p90_s."""
from bench.phases import prefill_p90_ms as read  # noqa: F401
