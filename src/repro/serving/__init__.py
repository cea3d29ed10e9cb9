"""Continuous-batching serving: request lifecycle, scheduler, slot cache,
budget planning, the step-driven engine that ties them to the model stack,
and the asyncio streaming front over it."""
from repro.serving.async_engine import AsyncEngine
from repro.serving.budget import (
    EnginePlan,
    cache_bytes_per_token,
    param_bytes,
    plan_engine,
    plan_engine_report,
    slot_state_bytes,
)
from repro.serving.cache import (
    PageAllocator,
    PagedSlotCache,
    PoolExhausted,
    SlotCache,
    SwapState,
)
from repro.serving.core import EngineCore
from repro.serving.engine import Engine
from repro.serving.events import StepEvent, TokenDelta
from repro.serving.executor import (
    EngineSpec,
    Executor,
    LocalExecutor,
    resolve_engine_spec,
)
from repro.serving.prefix_cache import PrefixCache, PrefixMatch, token_digest
from repro.serving.reference import token_by_token_greedy
from repro.serving.request import (
    FinishReason,
    Request,
    RequestOutput,
    SamplingParams,
    Sequence,
    SequenceState,
    make_requests,
    percentile,
)
from repro.serving.runner import (ExecuteInput, ExecuteOutput, ModelRunner,
                                  init_serving_params)
from repro.serving.scheduler import Scheduler
from repro.serving.utils import EngineStats

__all__ = [
    "AsyncEngine",
    "Engine",
    "EngineCore",
    "EnginePlan",
    "EngineSpec",
    "EngineStats",
    "ExecuteInput",
    "ExecuteOutput",
    "Executor",
    "FinishReason",
    "LocalExecutor",
    "ModelRunner",
    "PageAllocator",
    "PagedSlotCache",
    "PoolExhausted",
    "PrefixCache",
    "PrefixMatch",
    "Request",
    "RequestOutput",
    "SamplingParams",
    "Scheduler",
    "Sequence",
    "SequenceState",
    "SlotCache",
    "StepEvent",
    "SwapState",
    "TokenDelta",
    "cache_bytes_per_token",
    "init_serving_params",
    "make_requests",
    "param_bytes",
    "percentile",
    "plan_engine",
    "plan_engine_report",
    "resolve_engine_spec",
    "slot_state_bytes",
    "token_by_token_greedy",
    "token_digest",
]
