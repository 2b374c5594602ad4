"""Runtime support: fault tolerance (retries, heartbeats, preemption,
stragglers) and program spans (``runtime.trace``)."""
from .fault_tolerance import Heartbeat, PreemptionGuard, StragglerMonitor, retry

__all__ = ["Heartbeat", "PreemptionGuard", "StragglerMonitor", "retry"]
