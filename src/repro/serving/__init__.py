from .autoscale import Autoscaler, AutoscalePolicy, InstanceSchedule
from .energy import EnergyMeter, MeterBank, conservation_violations
from .engine import (DrainTruncatedError, PoolEngine, resolve_prefill_chunk,
                     scaled_prefill_chunk)
from .fleetsim import (FleetSim, PoolGroup, PoolSummary, SimVsAnalytical,
                       analytical_decode_tok_per_watt, prepare_spec,
                       run_fleet_grid, simulate_spec, trace_requests)
from .models import ModelBinding, ModelProfileRegistry
from .request import Request, sample_diurnal_trace, synthetic_requests
from .router import ContextRouter, RouterPolicy
from .soa import BatchedPoolEngine
from .telemetry import (TraceRecorder, build_timeline, phase_totals,
                        reconcile_energy, to_perfetto)

__all__ = ["EnergyMeter", "MeterBank", "PoolEngine", "BatchedPoolEngine",
           "TraceRecorder", "build_timeline", "phase_totals",
           "reconcile_energy", "to_perfetto", "conservation_violations",
           "Request", "synthetic_requests", "sample_diurnal_trace",
           "Autoscaler", "AutoscalePolicy", "InstanceSchedule",
           "ContextRouter", "RouterPolicy", "FleetSim", "PoolGroup",
           "PoolSummary", "SimVsAnalytical",
           "analytical_decode_tok_per_watt", "simulate_spec",
           "trace_requests", "ModelBinding", "ModelProfileRegistry",
           "DrainTruncatedError", "resolve_prefill_chunk",
           "scaled_prefill_chunk", "prepare_spec", "run_fleet_grid"]
