"""repro.api: the unified FLIP query surface.

    import flip                       # or: from repro import api as flip

    prog = flip.Program.get("sssp")   # algebra + numpy oracle, together
    plan = flip.ExecutionPlan(mode="data", tile=128)
    cq = flip.compile(graph, prog, plan)
    result = cq.query([0, 5, 9])      # QueryResult: attrs/steps/plan/...

Everything the fragmented `FlipEngine.run*` surface did -- solo runs,
batched multi-query fixpoints, shard_map distribution, serving-style
bucketed dispatch, streaming updates with incremental recompute -- is
one `compile` + `query` pair driven by a validated `ExecutionPlan`,
over the engine's one executor, `FlipEngine.execute`.
"""
from repro.api.plan import (ExecutionPlan, plan_from_cli,
                            resolve_cli_engine)
from repro.api.program import Program
from repro.api.session import CompiledQuery, QueryResult, compile
from repro.core.engine import WarmStart
from repro.obs.telemetry import DispatchTelemetry, QueryTelemetry
from repro.resilience.errors import (BackendFailure, CapacityExceeded,
                                     ConvergenceFailure, DeadlineExceeded,
                                     FlipError, InvalidRequest)

__all__ = [
    "ExecutionPlan", "Program", "CompiledQuery", "QueryResult",
    "WarmStart", "compile", "plan_from_cli", "resolve_cli_engine",
    "QueryTelemetry", "DispatchTelemetry",
    "FlipError", "InvalidRequest", "CapacityExceeded",
    "DeadlineExceeded", "ConvergenceFailure", "BackendFailure",
]
