"""repro.conv — plan/execute convolution engine.

    from repro.conv import plan_conv
    plan = plan_conv(x.shape, k.shape, padding=1)    # cached
    y = plan(x, k)

See docs/conv_api.md for the backend/schedule matrix and migration notes
from the deprecated ``fft_conv2d`` / ``fft_conv2d_pallas`` entry points.
"""
from repro.conv.registry import (
    BackendInfo, ScheduleInfo, register_backend, register_schedule,
    get_backend, get_schedule, available_backends, available_schedules,
)
from repro.conv.epilogue import Epilogue
from repro.conv.plan import (
    ConvPlan, PreparedConv, plan_conv, conv2d,
    plan_cache_info, clear_plan_cache, plan_cache_capacity,
    prepared_cache_info, clear_prepared_cache,
)
from repro.conv.registry import backend_schedule_pairs
from repro.conv.stages import stage_trace
from repro.conv.netplan import (
    NetworkConv, NetworkPlan, NetworkProfile, PreparedNetwork,
    BucketedNetworkPlan, plan_network,
)
from repro.conv.export import (
    ArtifactMismatch, LoadedConv, LoadedNetwork, export_network,
    load_network, plan_fingerprint,
)
from repro.conv.analyze import (
    PlanProfile, CheckReport, Violation, analyze, register_invariant,
    invariants_for,
)
from repro.conv import backends as _backends
from repro.conv import autotune
from repro.conv.autotune import TunedConfig, autotune_info

_backends.register_builtin()

__all__ = [
    "ConvPlan", "PreparedConv", "plan_conv", "conv2d", "Epilogue",
    "NetworkConv", "NetworkPlan", "NetworkProfile", "PreparedNetwork",
    "BucketedNetworkPlan", "plan_network",
    "ArtifactMismatch", "LoadedConv", "LoadedNetwork", "export_network",
    "load_network", "plan_fingerprint",
    "plan_cache_info", "clear_plan_cache", "plan_cache_capacity",
    "prepared_cache_info", "clear_prepared_cache",
    "stage_trace",
    "PlanProfile", "CheckReport", "Violation", "analyze",
    "register_invariant", "invariants_for",
    "autotune", "TunedConfig", "autotune_info",
    "BackendInfo", "ScheduleInfo",
    "register_backend", "register_schedule",
    "get_backend", "get_schedule",
    "available_backends", "available_schedules", "backend_schedule_pairs",
]
