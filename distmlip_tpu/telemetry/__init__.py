"""Telemetry: structured step records, trace annotations, pluggable sinks.

One pipeline replaces the ad-hoc timing that used to live in
``utils.profiling.StepTimer`` + ``DistPotential.last_timings``:

- ``StepRecord`` — the typed per-step schema (timings, graph shape,
  capacity occupancy, halo volumes, cache behavior, device memory);
- ``Telemetry`` + sinks (``AggregatingSink``, ``JsonlSink``,
  ``StderrSummarySink``) — where records go;
- ``annotate``/``scope``/``device_trace`` — xprof timeline names on the
  host and jit hot paths; ``STAGES`` names the stages the models scope,
  ``stage_tables()`` says after a tracing session which compiled
  instruction belongs to which; ``phase``/``log_phase``/``phases`` — the
  always-on log of what happens once (import, graph build, compile);
- ``report`` — offline aggregation of a JSONL run
  (``tools/telemetry_report.py``).

Quick start::

    from distmlip_tpu.telemetry import Telemetry, JsonlSink, AggregatingSink

    tel = Telemetry([JsonlSink("run.jsonl"), AggregatingSink()])
    pot = DistPotential(model, params, telemetry=tel)
    ...  # run MD / relax / calculate
    print(tel.sinks[1].summary())
    tel.close()
"""

from .record import PHASE_KEYS, StepRecord, TrainRecord
from .sinks import (AggregatingSink, JsonlSink, StderrSummarySink, Telemetry,
                    TelemetrySink)
from .stages import STAGES
from .trace import (annotate, device_trace, log_phase, note_dispatch, phase,
                    phases, reset_phases, scope, set_tracing, stage_tables,
                    tracing_enabled)

__all__ = [
    "PHASE_KEYS",
    "StepRecord",
    "TrainRecord",
    "Telemetry",
    "TelemetrySink",
    "AggregatingSink",
    "JsonlSink",
    "StderrSummarySink",
    "annotate",
    "scope",
    "device_trace",
    "set_tracing",
    "tracing_enabled",
    "note_dispatch",
    "stage_tables",
    "phase",
    "log_phase",
    "phases",
    "reset_phases",
    "STAGES",
]
