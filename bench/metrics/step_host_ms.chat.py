"""step_host_ms.chat: Engine host: host time of Engine.step outside its
dispatch-to-sync (prefill_s, decode_s) per mixed step or decode chunk, in
the window. Moves itl_p95_ms."""
from bench.phases import step_host_ms as read  # noqa: F401
