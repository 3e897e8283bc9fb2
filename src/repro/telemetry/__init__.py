"""Unified telemetry: span tracing, metrics, Perfetto export, audit chain.

The single entry point every instrumented layer uses::

    from repro.telemetry import get_tracer

    with get_tracer().span("stage.train", stage=k, store="coded") as sp:
        ...
        sp.annotate(cost_units=cost)

``get_tracer()`` returns a no-op tracer until ``configure(enabled=True)``
installs a recording one — the hot path pays nothing when disabled.  See
``tracer`` (spans, dual clocks, determinism), ``metrics`` (registry),
``export`` (Perfetto/JSONL/summary), and ``audit`` (hash-chained
unlearning event log).
"""
from repro.telemetry.audit import (
    GENESIS,
    AuditChainError,
    AuditLog,
    chain_hash,
    journal_chain,
    verify_chain,
    verify_journal,
)
from repro.telemetry.export import (
    render_tree,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)
from repro.telemetry.metrics import MetricsRegistry, NullMetrics
from repro.telemetry.tracer import (
    NULL_TRACER,
    NullTracer,
    Span,
    Tracer,
    configure,
    get_tracer,
    set_tracer,
)

__all__ = [
    "GENESIS",
    "AuditChainError",
    "AuditLog",
    "chain_hash",
    "journal_chain",
    "verify_chain",
    "verify_journal",
    "render_tree",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
    "write_jsonl",
    "MetricsRegistry",
    "NullMetrics",
    "NULL_TRACER",
    "NullTracer",
    "Span",
    "Tracer",
    "configure",
    "get_tracer",
    "set_tracer",
]
