from repro.serve.api import Request, RequestOutput, SamplingParams, StepRecord
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.kv_cache import PageAllocator, PagedKVCache
from repro.serve.scheduler import PagedScheduler

__all__ = [
    "ServeEngine",
    "ServeConfig",
    "Request",
    "RequestOutput",
    "SamplingParams",
    "StepRecord",
    "PageAllocator",
    "PagedKVCache",
    "PagedScheduler",
]
