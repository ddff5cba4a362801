"""Serving telemetry for the continuous query server.

Every resolved ticket carries a ``QueryTelemetry`` (queue time, dispatch
latency, retries, degradation flags) and the server aggregates a running
``ServerStats`` -- the observability contract the fault-injection tests
assert against.

``span`` marks a phase of the server's work as a ``jax.profiler``
trace annotation, on the same clock as the device's ops in a profiler
trace, and adds the phase's time on the server's own clock to a
``ServerStats`` field.  With no profiler running an annotation costs
about a microsecond; there is nothing to turn on or off.
"""

from __future__ import annotations

import contextlib
import dataclasses

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class QueryTelemetry:
    """Per-ticket timing and failure-handling record, attached to every
    resolved ticket (including structured rejections)."""
    submitted_at: float = 0.0
    dispatched_at: float | None = None      # None: never reached dispatch
    resolved_at: float = 0.0
    batch_size: int = 0                     # tickets in the ticket's batch
    retries: int = 0                        # failed kernel attempts
    splits: int = 0                         # alloc-pressure batch splits
    replans: int = 0                        # slab-mismatch re-plans
    degraded: bool = False                  # resolved on the host path

    @property
    def queue_time(self) -> float:
        """Admission -> dispatch (or rejection) wait."""
        end = (self.dispatched_at if self.dispatched_at is not None
               else self.resolved_at)
        return end - self.submitted_at

    @property
    def latency(self) -> float:
        """Admission -> resolution, the caller-visible total."""
        return self.resolved_at - self.submitted_at


@dataclasses.dataclass
class ServerStats:
    """Monotone counters over a server's lifetime (``QueryServer.stats``
    returns a snapshot copy)."""
    submitted: int = 0
    rejected_overloaded: int = 0
    rejected_invalid: int = 0
    resolved_ok: int = 0
    resolved_error: int = 0
    deadline_expired: int = 0
    ticks: int = 0
    batches: int = 0
    dispatch_retries: int = 0
    batch_splits: int = 0
    replans: int = 0
    rows_repatched: int = 0     # arena rows repatched by replan rungs
    host_fallbacks: int = 0
    max_batch: int = 0
    # "Type: message" of the exception that started the latest degraded
    # batch's failures: the compiler's or runtime's own words
    fallback_cause: str = ""
    # seconds of the similarity path's phases (``span``), each a span
    revalidate_s: float = 0.0   # serve.revalidate: the index's engine check
    lookup_s: float = 0.0       # serve.lookup: terms to candidate indices
    score_s: float = 0.0        # serve.score: topk_batch, answers fetched
    resolve_s: float = 0.0      # serve.resolve: answers to tickets
    sim_dispatches: int = 0     # device top-k dispatches of the engine
    sim_resident_dispatches: int = 0  # of them, query block held in VMEM
    # the boolean path: its phases' seconds (``span``), and what the
    # plans of the batches that reached the device held
    plan_s: float = 0.0         # serve.plan: planning at admission
    aggregate_s: float = 0.0    # serve.aggregate: execute_plans a tick
    seg_rows: int = 0           # rows of the device segments
    seg_groups: int = 0         # (query, chunk) groups reduced on device
    host_groups: int = 0        # non-empty (query, chunk) groups the
    #                             planner resolved on the host

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@contextlib.contextmanager
def span(name: str, stats: ServerStats | None = None,
         field: str | None = None, clock=None):
    """A ``TraceAnnotation`` named ``name`` around the block; with
    ``stats``, the block's time on ``clock`` (an object with ``now()``)
    is added to ``stats.<field>``, also when the block raises."""
    with TraceAnnotation(name):
        if stats is None:
            yield
            return
        t0 = clock.now()
        try:
            yield
        finally:
            setattr(stats, field, getattr(stats, field) + clock.now() - t0)
