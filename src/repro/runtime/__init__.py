"""Distributed runtime: fault tolerance, straggler mitigation, pipeline parallelism."""
from .supervisor import StepWatchdog, detect_stragglers, Supervisor
from .faults import FaultInjector
from .pipeline import pipeline_apply
from .tracing import span
from .traffic import (WallClock, VirtualClock, poisson_arrivals,
                      burst_arrivals, ramp_arrivals, make_arrivals)
