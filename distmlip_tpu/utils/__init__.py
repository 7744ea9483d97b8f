from .checkpoint import save_params, load_params
from .flops import (device_peaks, mfu, model_flop_estimate,
                    peak_flops_per_device)
from .memory import (device_bytes_limit, device_memory_stats,
                     hbm_usage_frac, measured_peak_bytes)
from .profiling import StepTimer, device_trace

__all__ = ["save_params", "load_params", "StepTimer", "device_trace",
           "model_flop_estimate", "peak_flops_per_device", "mfu",
           "device_peaks",
           "device_memory_stats", "hbm_usage_frac", "device_bytes_limit",
           "measured_peak_bytes"]
