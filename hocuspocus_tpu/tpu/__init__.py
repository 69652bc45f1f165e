# Lazy symbol resolution (PEP 562): importing this package must stay
# cheap and device-free. The merge-plane modules pull in the kernel
# stack, and a wedged TPU runtime can block device discovery forever —
# the plane supervisor (supervisor.py) runs those imports in a worker
# thread under a deadline, which only works if nothing here imports
# them eagerly.
_LAZY = {
    "MergePlane": ("merge_plane", "MergePlane"),
    "TpuMergeExtension": ("merge_plane", "TpuMergeExtension"),
    "ShardedTpuMergeExtension": ("sharded_extension", "ShardedTpuMergeExtension"),
    "MultiDeviceMergeExtension": ("cells", "MultiDeviceMergeExtension"),
    "DevicePlacement": ("cells", "DevicePlacement"),
    "PlaneSupervisor": ("supervisor", "PlaneSupervisor"),
    "ResidencyManager": ("residency", "ResidencyManager"),
    "SupervisedTpuMergeExtension": ("supervisor", "SupervisedTpuMergeExtension"),
    "CircuitBreaker": ("supervisor", "CircuitBreaker"),
    # adaptive merge scheduling (tpu/scheduler.py): these import no
    # kernel/JAX modules, so resolving them stays boot-safe
    "DeviceLane": ("scheduler", "DeviceLane"),
    "BatchGovernor": ("scheduler", "BatchGovernor"),
    "get_device_lane": ("scheduler", "get_device_lane"),
    "reset_device_lane": ("scheduler", "reset_device_lane"),
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from importlib import import_module

    value = getattr(import_module(f".{entry[0]}", __name__), entry[1])
    globals()[name] = value  # cache: resolve each symbol once
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
