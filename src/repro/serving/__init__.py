"""Real serving runtime: paged KV pool, jitted model exec, continuous-
batching engine, threaded engine drivers, the synchronous GoRouting service
controller, and the async streaming front-end."""
from .kv_pool import PagedKVPool
from .prefix_cache import RadixPrefixCache
from .spec import DraftRunner
from .transfer import TransferDone, TransferWorker
from .engine import (DriverFailed, Engine, EngineDriver, EngineStats,
                     HandoffAdopted, HandoffDropped, HandoffEvent,
                     HandoffPayload, StepEvent, TokenEvent)
from .dispatch import RouterBook
from .service import ServiceController, ServiceConfig
from .frontend import (AdmissionError, FrontendConfig, RequestStream,
                       ServiceFrontend)

__all__ = ["PagedKVPool", "RadixPrefixCache", "DraftRunner", "TransferDone",
           "TransferWorker", "DriverFailed", "Engine", "EngineDriver",
           "EngineStats", "HandoffAdopted", "HandoffDropped",
           "HandoffEvent", "HandoffPayload", "StepEvent", "TokenEvent",
           "RouterBook",
           "ServiceController", "ServiceConfig", "AdmissionError",
           "FrontendConfig", "RequestStream", "ServiceFrontend"]
