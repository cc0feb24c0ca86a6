"""The paged serve launcher end to end on a reduced config: every request
of the seeded Poisson mix gets exactly its scripted token count, the pool
drains, and the service plans against exactly the engine's pool."""
from __future__ import annotations

import pytest

from repro.configs import get_config
from repro.launch.serve import _pool_memory_model, run_paged_engine_backend
from repro.workload.generator import poisson_workload


@pytest.mark.parametrize("dtype_bytes", [2, 4])
def test_pool_memory_model_theta_is_the_pool(dtype_bytes):
    cfg = get_config("smollm-135m")
    mem = _pool_memory_model(cfg, 128 * 16, dtype_bytes, max_len=200,
                             max_gen=32)
    assert mem.theta == 128 * 16 * cfg.kv_bytes_per_token(dtype_bytes)
    assert mem.delta == cfg.kv_bytes_per_token(dtype_bytes)


def test_paged_launcher_serves_every_request_on_script():
    out = run_paged_engine_backend("smollm-135m", 2.0, 2.0, "magnus-paged",
                                   0, prefix_cache=True, dtype="bfloat16")
    n = len(poisson_workload(2.0, 2.0, seed=0, max_len=200, max_gen=32))
    assert n > 0
    assert out["requests"] == n
    assert out["off_script"] == 0
