from repro.core.strategy import ClientUpdate, ServerState, get_strategy
from .async_agg import (AsyncAggregator, STALENESS_SCHEDULES,
                        make_staleness_fn)
from .chaos import FaultPlan
from .client import (LocalFitResult, make_local_fit, merge_base_params,
                     softmax_xent, split_base_params)
from .comm import BufferedUpdate, DedupWindow, RetryPolicy, UpdateBuffer
from .durability import DurableAggregator, WriteAheadLog
from .selection import ClientLatencyModel, select_clients
from .simulator import (AsyncFLConfig, FLConfig, FLHistory,
                        run_async_simulation, run_simulation)

__all__ = ["LocalFitResult", "make_local_fit", "merge_base_params",
           "softmax_xent", "split_base_params", "select_clients",
           "FLConfig", "FLHistory", "run_simulation", "ClientUpdate",
           "ServerState", "get_strategy", "AsyncAggregator",
           "STALENESS_SCHEDULES", "make_staleness_fn", "AsyncFLConfig",
           "run_async_simulation", "ClientLatencyModel", "UpdateBuffer",
           "BufferedUpdate", "DedupWindow", "RetryPolicy",
           "DurableAggregator", "WriteAheadLog", "FaultPlan"]
