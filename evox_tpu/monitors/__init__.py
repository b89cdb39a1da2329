from .eval_monitor import EvalMonitor, EvalMonitorState
from .pop_monitor import PopMonitor
from .evoxvis_monitor import EvoXVisMonitor
from .checkpoint_monitor import CheckpointMonitor
from .profiler import StepTimerMonitor, trace as profiler_trace
from .telemetry import TelemetryMonitor, TelemetryState
from .lineage import LineageMonitor, LineageState
from . import profiler

__all__ = [
    "EvalMonitor",
    "EvalMonitorState",
    "PopMonitor",
    "EvoXVisMonitor",
    "CheckpointMonitor",
    "StepTimerMonitor",
    "TelemetryMonitor",
    "TelemetryState",
    "LineageMonitor",
    "LineageState",
    "profiler_trace",
    "profiler",
]
