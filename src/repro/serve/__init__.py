"""repro.serve -- serving layers over the Roaring engine.

``query_server`` is the fault-tolerant continuous batcher (coalesced
multi-query dispatch, admission control, deadlines, kernel->host
degradation); ``faults`` its deterministic fault-injection harness;
``telemetry`` the per-ticket/server observability records and the
trace spans of the server's phases.
"""

from repro.serve.faults import (AllocPressure, DispatchFault, FakeClock,
                                FaultError, FaultInjector, SlabMismatch,
                                SystemClock)
from repro.serve.query_server import (DEADLINE, ERROR, INVALID, OK,
                                      OVERLOADED, Query, QueryServer,
                                      Ticket, TicketResult)
from repro.serve.telemetry import QueryTelemetry, ServerStats

__all__ = [
    "Query", "QueryServer", "Ticket", "TicketResult",
    "OK", "OVERLOADED", "INVALID", "DEADLINE", "ERROR",
    "FaultError", "DispatchFault", "SlabMismatch", "AllocPressure",
    "FaultInjector", "FakeClock", "SystemClock",
    "QueryTelemetry", "ServerStats",
]
