"""Test harness configuration.

Forces JAX onto the host platform with 8 virtual devices BEFORE jax is
imported anywhere, so every sharding/collective test runs against a simulated
8-chip mesh (SURVEY.md §4: the CPU-device-simulation analog of the reference's
fake-GPU yamls).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Load calibration: this box runs the whole cluster under test on one
# core, so heartbeat/startup threads starve for seconds under a full
# suite.  The scale multiplies the liveness-patience flags
# (config._SCALED_FLAGS) in every daemon (env-inherited) AND the
# explicit get/wait timeouts tests pass (shim below).
os.environ.setdefault("RAY_TPU_TIMEOUT_SCALE", "4.0")
_TIMEOUT_SCALE = float(os.environ["RAY_TPU_TIMEOUT_SCALE"])

import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _scale_test_timeouts():
    """Multiply explicit ray_tpu.get/wait timeouts by the load scale —
    test constants are written for an idle box."""
    import ray_tpu
    real_get, real_wait = ray_tpu.get, ray_tpu.wait

    def get(refs, *, timeout=None, **kw):
        if timeout is not None:
            timeout = timeout * _TIMEOUT_SCALE
        return real_get(refs, timeout=timeout, **kw)

    def wait(refs, **kw):
        if kw.get("timeout") is not None:
            kw["timeout"] = kw["timeout"] * _TIMEOUT_SCALE
        return real_wait(refs, **kw)

    ray_tpu.get = get
    ray_tpu.wait = wait
    yield
    ray_tpu.get = real_get
    ray_tpu.wait = real_wait


@pytest.fixture
def ray_start_regular():
    """Start a fresh single-node ray_tpu instance for the test (head + 1 node)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-daemon simulated cluster (cf. reference cluster_utils.Cluster)."""
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster()
    yield cluster
    cluster.shutdown()


def record_recovery_row(row):
    """Under ``MICROBENCH_RECORD=1`` the chaos gates double as the data
    source for MICROBENCH.json's ``recovery`` section: the drain /
    failover / heal latencies they already assert against the
    recovery-SLO auditor ARE the numbers the bench table should cite,
    so recording them here keeps bench and gate from drifting.  Same
    merge-by-row-name idiom as benchmarks/scale_envelope.py — a partial
    re-run must not drop sibling rows, and collect_microbench's
    merge_preserve carries the whole section across refreshes."""
    import json
    if os.environ.get("MICROBENCH_RECORD") != "1":
        return
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MICROBENCH.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    sec = doc.setdefault("recovery", {})
    merged = {r.get("name"): r for r in sec.get("episodes", [])}
    merged[row.get("name")] = row
    sec["episodes"] = list(merged.values())
    sec["source"] = ("tests/test_preemption.py + tests/test_chaos.py "
                     "under MICROBENCH_RECORD=1: recovery-SLO auditor "
                     "episodes from injected chaos")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


@contextlib.contextmanager
def debug_sanitizers_enabled():
    """Run a block under BOTH runtime sanitizers
    (docs/static_analysis.md): the lock-order sanitizer and the
    shm-ring protocol checker, in this process and — via the inherited
    env — in every daemon/worker spawned inside the block.  Env is
    restored afterwards so the rest of a tier-1 run stays
    uninstrumented.  The chaos and compiled-DAG suites wrap their whole
    module in this via an autouse fixture."""
    from ray_tpu._private.analysis import lock_sanitizer
    keys = ("RAY_TPU_DEBUG_LOCKS", "RAY_TPU_DEBUG_CHANNELS")
    old = {k: os.environ.get(k) for k in keys}
    for k in keys:
        os.environ[k] = "1"
    lock_sanitizer.install()
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
