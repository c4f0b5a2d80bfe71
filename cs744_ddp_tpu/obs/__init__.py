"""Structured telemetry: per-step events, spans, gauges, run manifest.

The reference's only observability surface is stdout (the 20-iteration
windowed prints, ``/root/reference/src/Part 1/main.py:28-57``).  This package
adds a machine-readable layer BESIDE that surface — never instead of it: a
JSONL event log plus a run manifest and an end-of-run summary, written only
when the caller opts in (``--telemetry-out``).  Disabled is the default and
costs nothing: ``NULL`` is a stateless no-op recorder and every hot call
site guards on ``telemetry.enabled``.
"""

from .alerts import RULES as ALERT_RULES
from .alerts import Alert, AlertEngine
from .telemetry import (NULL, NULL_SPAN, NullTelemetry, Telemetry, git_sha,
                        percentile, read_run, span_log, summarize_events)
from .tracing import TraceContext

__all__ = ["ALERT_RULES", "Alert", "AlertEngine", "NULL", "NULL_SPAN",
           "NullTelemetry", "Telemetry", "TraceContext", "git_sha",
           "percentile", "read_run", "span_log", "summarize_events"]
