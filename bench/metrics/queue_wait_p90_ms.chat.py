"""queue_wait_p90_ms.chat: Scheduler: 90th percentile of admission minus
submit time (admission: the start of the step that computed the request's
first prompt chunk), in the window. Moves ttft_p90_s."""
from bench.phases import queue_wait_p90_ms as read  # noqa: F401
