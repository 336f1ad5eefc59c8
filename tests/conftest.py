"""Test harness (mirrors the reference's test strategy, SURVEY.md §4):
CPU backend with 8 virtual devices so ALL distributed logic runs with no TPU
(the reference's Gloo/CustomCPU fixture pattern)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield
    # amp.decorate activates a persistent dispatch-level AMP state; isolate it
    from paddle_tpu.framework import core as _core

    _core.set_active_amp(None)


# the serving/async suites run under the runtime sanitizer: any unexpected
# trace/compile/host-sync inside a steady-state region is a hard test error
_SANITIZED_MODULES = {
    "test_serving_engine",
    "test_paged_kv",
    "test_serving_fault",
    "test_async_pipeline",
    "test_observability",
    "test_spec_decode",
    "test_lora_serving",
    "test_fused_paged_attention",
    "test_kv_quant",
    "test_tp_serving",
    "test_autoscale_soak",
    "test_disagg_serving",
}


@pytest.fixture(autouse=True)
def _sanitized(request):
    if request.module.__name__ not in _SANITIZED_MODULES:
        yield
        return
    from paddle_tpu.analysis import sanitizer
    from paddle_tpu.framework import core as _core

    _core.set_flags({"FLAGS_debug_sanitize": True})
    sanitizer.reset()
    try:
        yield
        sanitizer.check()
    finally:
        sanitizer.reset()
        _core.set_flags({"FLAGS_debug_sanitize": False})


def finite_difference_grad(fn, x, eps=1e-3):
    """Numeric gradient of scalar fn at numpy array x (OpTest check_grad)."""
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        xp = x.copy()
        xp[idx] += eps
        xm = x.copy()
        xm[idx] -= eps
        g[idx] = (fn(xp.astype(np.float32)) - fn(xm.astype(np.float32))) / (2 * eps)
        it.iternext()
    return g
