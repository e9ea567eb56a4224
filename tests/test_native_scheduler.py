"""Native C++ cluster scheduler vs Python fallback: same semantics."""

import pytest

from ray_tpu._core.scheduler import (NativeClusterScheduler,
                                     PyClusterScheduler, native_available)

SCHEDULERS = [PyClusterScheduler]
if native_available():
    SCHEDULERS.append(NativeClusterScheduler)


@pytest.fixture(params=SCHEDULERS, ids=lambda c: c.__name__)
def sched(request):
    return request.param(spill_threshold=0.5, top_k=2)


def test_local_first_under_threshold(sched):
    sched.update_node("local", {"CPU": 8}, {"CPU": 8})
    sched.update_node("other", {"CPU": 8}, {"CPU": 8})
    # local stays preferred while post-placement utilization <= 0.5
    assert sched.best_node({"CPU": 2}, local_id="local") == "local"


def test_spills_when_local_hot(sched):
    sched.update_node("local", {"CPU": 8}, {"CPU": 2})   # 75% used
    sched.update_node("cold", {"CPU": 8}, {"CPU": 8})
    assert sched.best_node({"CPU": 1}, local_id="local") == "cold"


def test_infeasible_returns_none(sched):
    sched.update_node("a", {"CPU": 2}, {"CPU": 2})
    assert sched.best_node({"CPU": 4}) is None
    assert not sched.feasible_anywhere({"CPU": 4})
    assert sched.feasible_anywhere({"CPU": 2})


def test_feasible_anywhere_uses_total_not_available(sched):
    sched.update_node("a", {"CPU": 4}, {"CPU": 0})
    assert sched.best_node({"CPU": 1}) is None        # nothing available now
    assert sched.feasible_anywhere({"CPU": 1})        # but not infeasible


def test_custom_and_fractional_resources(sched):
    sched.update_node("t", {"CPU": 4, "TPU": 8, "slice": 1},
                      {"CPU": 3.5, "TPU": 8, "slice": 1})
    assert sched.best_node({"CPU": 0.5, "TPU": 4}) == "t"
    assert sched.best_node({"CPU": 3.75}) is None     # 3.75 > 3.5 available
    assert sched.best_node({"slice": 1, "CPU": 0.1}) == "t"


def test_dead_nodes_skipped(sched):
    sched.update_node("a", {"CPU": 4}, {"CPU": 4}, alive=False)
    sched.update_node("b", {"CPU": 4}, {"CPU": 1})
    assert sched.best_node({"CPU": 1}) == "b"
    sched.remove_node("b")
    assert sched.best_node({"CPU": 1}) is None
    assert sched.num_nodes() == 1


def test_top_k_rotation_spreads_ties(sched):
    sched.update_node("a", {"CPU": 8}, {"CPU": 8})
    sched.update_node("b", {"CPU": 8}, {"CPU": 8})
    picks = {sched.best_node({"CPU": 1}) for _ in range(8)}
    assert picks == {"a", "b"}   # top_k=2 rotates over equal candidates


def test_packing_prefers_fuller_node(sched):
    # hybrid under threshold packs: lowest post-placement utilization wins,
    # but among *under-threshold* nodes the scheduler is utilization-sorted;
    # the emptier node scores lower utilization and wins when no local given
    sched.update_node("busy", {"CPU": 10}, {"CPU": 3})
    sched.update_node("idle", {"CPU": 10}, {"CPU": 9})
    assert sched.best_node({"CPU": 1}) == "idle"


# --------------------------------------------------------------------------
# Chip bring-up guards (no cluster, no backend): what the node advertises,
# where compiles are cached, and that nothing hides the device.
# --------------------------------------------------------------------------

def _fake_host(tmp_path, *, accel=0, vfio=(), google_pci=False):
    dev, pci = tmp_path / "dev", tmp_path / "pci"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()                 # the control node
    for i in range(accel):
        (dev / f"accel{i}").touch()
    for g in vfio:
        (dev / "vfio" / str(g)).touch()
    for i, vendor in enumerate(["0x8086"] + ["0x1ae0"] * 4 * google_pci):
        (pci / f"0000:00:0{i}.0").mkdir(parents=True)
        (pci / f"0000:00:0{i}.0" / "vendor").write_text(vendor + "\n")
    return str(dev), str(pci)


@pytest.mark.parametrize("host,chips", [
    (dict(), 0),                                        # chipless sandbox
    (dict(accel=4), 4),                                 # accel driver
    (dict(vfio=(1,), google_pci=True), 1),              # one-chip v5e VM
    (dict(vfio=(0, 1, 2, 3), google_pci=True), 4),      # v5e 2x2 host
    (dict(vfio=(7,), google_pci=False), 0),             # foreign passthrough
    (dict(google_pci=True), 0),       # PCI functions alone are no chips
])
def test_detect_tpu_chips_from_device_nodes(tmp_path, host, chips):
    from ray_tpu.runtime.raylet import detect_tpu_chips
    dev, pci = _fake_host(tmp_path, **host)
    assert detect_tpu_chips(dev, pci) == chips


@pytest.mark.parametrize("env,found,want", [
    ({}, 4, 4.0),                                  # plain libtpu host
    ({"JAX_PLATFORMS": "tpu,cpu"}, 1, 1.0),
    ({"JAX_PLATFORMS": "cpu"}, 4, None),           # workers could not use it
    ({"TPU_CHIPS_PER_HOST": "8", "JAX_PLATFORMS": "cpu"}, 0, 8.0),
    ({"JAX_PLATFORMS": "cuda,cpu"}, 4, None),      # TPU not in the list
    ({}, 0, None),
])
def test_detect_resources_env_cases(monkeypatch, env, found, want):
    from ray_tpu.runtime import raylet
    for key in ("JAX_PLATFORMS", "TPU_CHIPS_PER_HOST"):
        monkeypatch.delenv(key, raising=False)
    for key, val in env.items():
        monkeypatch.setenv(key, val)
    monkeypatch.setattr(raylet, "detect_tpu_chips", lambda: found)
    assert raylet.detect_resources().get("TPU") == want


def test_compile_cache_env_set_is_untouched(monkeypatch):
    from ray_tpu._private import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert compile_cache.ensure_compile_cache() == "/somewhere/outside"
    import os
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/outside"


def test_compile_cache_default_is_fixed_path_under_checkout(monkeypatch):
    import os
    import sys

    from ray_tpu._private import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    # pinned to the CPU (this suite, chipless clusters): no cache at all
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.ensure_compile_cache() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")     # the chip machine
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.ensure_compile_cache() == want
    # exported, so every child process inherits the same directory
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert compile_cache.ensure_compile_cache() == want


def test_container_forwards_compile_cache_dir():
    from ray_tpu.runtime_env.container import wrap_worker_command
    cmd = wrap_worker_command(
        {"image": "img:1", "driver": "/bin/true"},
        ["python", "-m", "ray_tpu.runtime.worker_main"],
        session_dir="/tmp/sess", store_path="/dev/shm/store",
        env={"JAX_COMPILATION_CACHE_DIR": "/cache/jax"})
    assert "JAX_COMPILATION_CACHE_DIR=/cache/jax" in cmd
    assert "/cache/jax:/cache/jax" in cmd


def _busy_backend(*_a, **_k):
    raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                       "libtpu multi-process lockfile")


@pytest.mark.parametrize("impl", ["flash", "auto"])
def test_attention_raises_when_backend_query_raises(monkeypatch, impl):
    """A chip held by another process must surface, not turn an explicit
    kernel request into the interpreter or the XLA path."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention
    monkeypatch.setattr(jax, "devices", _busy_backend)
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="lockfile"):
        attention(q, q, q, impl=impl)


def test_paged_attention_dispatch_never_hides_the_device(monkeypatch):
    import jax

    from ray_tpu.ops import paged_attention as pa
    # lane-misaligned pool (2*head_dim = 32): only "auto" may pick XLA
    assert pa.resolve_paged_impl(32, "auto") == "xla"
    assert pa.resolve_paged_impl(32, "xla") == "xla"
    with pytest.raises(ValueError, match="128"):
        pa.resolve_paged_impl(32, "tpu")
    monkeypatch.setenv("RAY_TPU_PAGED_ATTENTION_IMPL", "tpu")
    with pytest.raises(ValueError, match="128"):
        pa.resolve_paged_impl(32, "auto")      # env counts as explicit
    monkeypatch.delenv("RAY_TPU_PAGED_ATTENTION_IMPL")
    assert pa.resolve_paged_impl(128, "tpu") == "tpu"
    monkeypatch.setattr(pa, "backend_platform", lambda: "tpu")
    assert pa.resolve_paged_impl(128, "auto") == "tpu"
    monkeypatch.undo()
    monkeypatch.setattr(jax, "devices", _busy_backend)
    with pytest.raises(RuntimeError, match="lockfile"):
        pa.resolve_paged_impl(128, "auto")


def test_zygote_refuses_to_fork_with_a_backend(monkeypatch):
    import sys
    import types

    from ray_tpu.runtime import worker_zygote as wz
    fake = types.SimpleNamespace(backends_are_initialized=lambda: False,
                                 _backends={})
    monkeypatch.setitem(sys.modules, "jax._src.xla_bridge", fake)
    wz._assert_no_backend()
    fake.backends_are_initialized = lambda: True
    fake._backends = {"tpu": object()}
    with pytest.raises(RuntimeError, match="tpu"):
        wz._assert_no_backend()


def test_stale_binaries_with_failed_rebuild_raise(monkeypatch):
    import subprocess

    from ray_tpu._core import buildcheck

    def no_toolchain(*_a, **_k):
        raise FileNotFoundError("make")

    monkeypatch.setattr(buildcheck, "_checked", False)
    monkeypatch.setattr(buildcheck, "source_hash", lambda: "f" * 64)
    monkeypatch.setattr(buildcheck, "write_stamp", lambda: None)
    monkeypatch.setattr(subprocess, "run", no_toolchain)
    with pytest.raises(RuntimeError, match="rebuild failed"):
        buildcheck.ensure_fresh()
    assert buildcheck._checked is False     # the next call checks again


def test_python_scheduler_fallback_warns_once(monkeypatch, caplog):
    import logging

    from ray_tpu._core import scheduler
    monkeypatch.setattr(scheduler, "_lib", None)
    scheduler._warn_fallback.cache_clear()
    log = logging.getLogger("ray_tpu.scheduler")
    monkeypatch.setattr(log, "propagate", True)
    with caplog.at_level(logging.WARNING):
        first = scheduler.make_scheduler()
        scheduler.make_scheduler()
    assert isinstance(first, PyClusterScheduler)
    assert sum("did not load" in r.getMessage()
               for r in caplog.records) == 1


@pytest.mark.parametrize("nodes,workers,want", [
    ([], 1, None),                                    # CPU cluster
    ([{"CPU": 4.0}], 2, None),
    ([{"CPU": 13.0, "TPU": 1.0}], 1, {"TPU": 1.0}),
    ([{"TPU": 4.0}], 1, {"TPU": 4.0}),                # all chips of the host
    ([{"TPU": 4.0}, {"TPU": 4.0}], 2, {"TPU": 4.0}),
    ([{"TPU": 4.0}], 2, ValueError),      # two TPU processes on one host
])
def test_sharded_trainer_lease(monkeypatch, nodes, workers, want):
    import ray_tpu
    from ray_tpu.train.sharded.executor import tpu_lease_per_worker
    monkeypatch.setattr(ray_tpu, "nodes", lambda: [
        {"alive": True, "resources": r} for r in nodes]
        + [{"alive": False, "resources": {"TPU": 8.0}}])
    if want is ValueError:
        with pytest.raises(ValueError, match="one host"):
            tpu_lease_per_worker(workers)
    else:
        assert tpu_lease_per_worker(workers) == want


def test_peaks_table_refuses_unknown_devices():
    """The one table of peaks (``chipbench/lib/peaks.py``): the v5e's
    published bf16 peak, and no default for a kind it does not hold."""
    from chipbench.lib.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    for kind in ("cpu", "TPU v9"):
        with pytest.raises(SystemExit):
            peaks_for(kind)


def test_gcs_health_check_credits_its_own_pause():
    """A whole-host stall (libtpu start on the v5e VM: 3-6 s) pauses the
    monitor as well as the nodes: it must not read its own pause as
    their death, and must still catch a node that is really silent."""
    import types

    from ray_tpu.runtime.gcs import GcsServer
    period, threshold = 0.25, 8
    gcs = types.SimpleNamespace(_nodes={
        "fresh": {"alive": True, "last_heartbeat": 100.0},
        "silent": {"alive": True, "last_heartbeat": 90.0},
        "gone": {"alive": False, "last_heartbeat": 0.0}})
    expired = GcsServer._expired_nodes
    # monitor woke on time, 1 s later: only the long-silent node expires
    assert expired(gcs, 101.0, 0.0, period, threshold) == ["silent"]
    # monitor (and everyone else) frozen for 6 s after t=100: nobody new
    gcs._nodes["silent"]["last_heartbeat"] = 99.9
    assert expired(gcs, 106.0, 6.0 - period, period, threshold) == []
    # ...but the same 6 s of silence with the monitor awake is a death
    gcs._nodes["fresh"]["last_heartbeat"] = 100.0
    gcs._nodes["silent"]["last_heartbeat"] = 99.9
    assert expired(gcs, 106.0, 0.0, period, threshold) == \
        ["fresh", "silent"]


def test_strict_spread_skips_nodes_already_holding_a_bundle():
    """A roomy node (a many-core head: 8 CPUs here, 1 on the box these
    tests were written on) sorted first for EVERY bundle, drew two, and
    STRICT_SPREAD then refused the whole plan forever."""
    from ray_tpu.runtime.gcs import GcsServer
    nodes = [{"node_id": "head", "available": {"CPU": 8.0}},
             {"node_id": "n2", "available": {"CPU": 4}},
             {"node_id": "n3", "available": {"CPU": 4}}]
    pack = GcsServer._pack_bundles_on
    placed = pack(None, [{"CPU": 3}, {"CPU": 3}], "STRICT_SPREAD", nodes)
    assert placed is not None and len(set(placed)) == 2
    assert pack(None, [{"CPU": 3}] * 4, "STRICT_SPREAD", nodes) is None
    # plain SPREAD still may double up when it has to
    assert pack(None, [{"CPU": 3}] * 4, "SPREAD", nodes) is not None
