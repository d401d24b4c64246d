from repro.serving.engine import (PrefillCursor, Request, SamplingParams,
                                  ServingEngine, make_serve_step)
from repro.serving.fabric import (CapacityError, ClusterRegistry,
                                  LocalProcessBackend, Mailbox,
                                  MailboxError, MockBackend,
                                  RemoteScheduler, ReplicaWorker,
                                  SchedulerBackend, SlurmBackend,
                                  WorkerSpec, collect_fabric_traces,
                                  launch_fabric_replicas,
                                  shutdown_fabric)
from repro.serving.faults import (FAULT_KINDS, FAULT_SITES, FaultInjector,
                                  FaultPlan, FaultSpec, InjectedFault,
                                  ReplicaCrashed)
from repro.serving.gateway import (CapsuleReplica, DegradationPolicy,
                                   Overloaded, ReplicaGateway,
                                   RequestFailed, RetryPolicy,
                                   launch_capsule_replicas)
from repro.serving.health import (DEAD, DEGRADED, HEALTHY, QUARANTINED,
                                  HealthConfig, HealthMonitor)
from repro.serving.kvcache import KVBlockPool, OutOfBlocks, PagedKVCache
from repro.serving.metrics import (ServingMetrics, atomic_write_json,
                                   merge_summaries)
from repro.serving.prefix_cache import PrefixCache, PrefixCacheStats
from repro.serving.profiling import RecompilationTracker
from repro.serving.scheduler import Scheduler
from repro.serving.slo import (SLOConfig, SLOMonitor, SLOPolicy,
                               SlidingWindow, TenantStats,
                               merge_tenant_summaries,
                               merge_window_summaries)
from repro.serving.tracing import (EVENT_KINDS, FAULT_EVENT_KINDS, SPANS,
                                   Tracer, export_chrome_trace, export_jsonl,
                                   merge_traces, to_chrome_trace,
                                   validate_event)
