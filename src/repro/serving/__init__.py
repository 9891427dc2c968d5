from repro.serving.chaos import (FAULT_POINTS, ChaosError,  # noqa: F401
                                 ChaosInjector)
from repro.serving.config import CacheSpec, EngineConfig, MeshSpec  # noqa: F401
from repro.serving.engine import (Engine, FinishReason,  # noqa: F401
                                  ModelRunner, Request,
                                  RequestResult, Scheduler, ServeStats,
                                  bytes_tokenizer_decode,
                                  bytes_tokenizer_encode)
from repro.serving.paging import PagePool, PrefixMatch, RadixCache  # noqa: F401
