"""Eval-lifecycle tracing: flight-recorder spans with p99 stage
attribution (see trace/README.md).

Every evaluation yields a span tree — broker wait, dispatch-pipeline
accumulate/launch, scheduler invoke, matrix build, device dispatch,
plan submit/evaluate/commit, FSM alloc upsert — recorded into a
bounded lock-striped ring buffer (recorder.py). Exposed via
``/v1/agent/trace`` (recent + tail-kept traces), ``/v1/metrics``
(Prometheus exposition of the shared telemetry registry), and the
per-stage latency table in ``server.stats()["trace"]``.

Call sites use the module-level helpers below against the process-wide
recorder; all of them are no-ops when the recorder is disabled and
never raise into the instrumented path.
"""

import contextlib
import sys

from .recorder import FlightRecorder  # noqa: F401
from .span import (  # noqa: F401
    ALL_STAGES,
    CLIENT_PATH_STAGES,
    DEVICE_IDLE_STAGES,
    HTTP_STAGES,
    LIFECYCLE_CORE_STAGES,
    SELF_SUFFIX,
    STAGE_ALLOC_UPSERT,
    STAGE_API_REGISTER,
    STAGE_BASE_DELTA,
    STAGE_BATCH_CLAIMS,
    STAGE_BATCH_HANDOVER,
    STAGE_BATCH_QUEUES,
    STAGE_BROKER_WAIT,
    STAGE_DEFRAG_SOLVE,
    STAGE_DEVICE_DISPATCH,
    STAGE_DEVICE_SOLVE,
    STAGE_DEVICE_TRANSFER,
    STAGE_DISPATCH_ACCUMULATE,
    STAGE_DISPATCH_LAUNCH,
    STAGE_DISPATCH_POOL_WAIT,
    STAGE_EVAL_UNCOVERED,
    STAGE_EVAL_UPDATE,
    STAGE_FEASIBILITY_BUILD,
    STAGE_GANG_BUILD,
    STAGE_GANG_REJECTED,
    STAGE_GANG_SELECT,
    STAGE_GANG_SOLVE,
    STAGE_IDLE_BATCH_WAIT,
    STAGE_IDLE_NO_WORK,
    STAGE_IDLE_STACK,
    STAGE_MATRIX_BUILD,
    STAGE_MATRIX_COMPRESS,
    STAGE_MATRIX_PLAN_PATCH,
    STAGE_MATRIX_UPDATE,
    STAGE_MIGRATE_PLACE,
    STAGE_PLAN_COMMIT,
    STAGE_PLAN_EVALUATE,
    STAGE_PLAN_QUEUE_WAIT,
    STAGE_PLAN_SUBMIT,
    STAGE_PREEMPT_SELECT,
    STAGE_PREEMPT_SOLVE,
    STAGE_PREEMPT_VICTIMS,
    STAGE_READ_DELIVER,
    STAGE_READ_NOTIFY_LAG,
    STAGE_READ_PARK,
    STAGE_READ_SERVE,
    STAGE_READ_SERVE_CPU,
    STAGE_READ_SERVE_WAIT,
    STAGE_RUNTIME_GC_FULL_PAUSE,
    STAGE_RUNTIME_GC_PAUSE,
    STAGE_RUNTIME_GIL_WAIT,
    STAGE_SCHED_PROCESS,
    STAGE_SCHED_RECONCILE,
)

# The process-wide recorder every instrumentation site uses. Module
# level so the disabled check is two attribute loads + a branch (the
# same shape as chaos.enabled).
_recorder = FlightRecorder()


def get_recorder() -> FlightRecorder:
    return _recorder


_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str, **kwargs):
    """A `jax.profiler.TraceAnnotation`: a named region of this host
    thread on the profiler's clock, the same one the device's events
    are on, so that an idle gap of the device can be named by what the
    host did across it (tools/traceconv.py --xplane). For batch-level
    regions only (the `nomad.*` names in README.md), never per eval.
    While no profile is being taken it costs one TraceMe construction
    (~0.6 us); in a process that has not loaded JAX (an agent without
    -tpu) it is nothing, and this module does not load it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_ANNOTATION
    return jax.profiler.TraceAnnotation(name, **kwargs)


def mark(eval_id: str, trace_id: str = "") -> None:
    _recorder.mark(eval_id, trace_id)


def record_since_mark(eval_id: str, stage: str, ann=None) -> None:
    _recorder.record_since_mark(eval_id, stage, ann)


def record_span(eval_id: str, stage: str, t0: float, t1=None, ann=None,
                trace_id: str = "", create: bool = True) -> None:
    _recorder.record_span(eval_id, stage, t0, t1, ann, trace_id, create)


def annotate_fault(eval_id: str, site: str, seq: int, kind: str) -> None:
    _recorder.annotate_fault(eval_id, site, seq, kind)


def complete(eval_id: str, status: str = "complete") -> None:
    _recorder.complete(eval_id, status)
