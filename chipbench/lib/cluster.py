"""Starting and stopping the program's cluster around one run, as
``chip_smoke.py`` does it (the only code that had met the chip)."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT_DIR = os.path.join(ROOT, ".chipbench_out")


def start_cluster(chips: int, object_store_bytes: int, allow_cpu: bool):
    """``ray_tpu.init()`` with no ``num_tpus=``: detection must find the
    chips the cell asks for, or the run is refused."""
    import ray_tpu
    ray_tpu.init(object_store_memory=object_store_bytes,
                 system_config={"actor_creation_timeout_s": 1100.0})
    found = ray_tpu.cluster_resources().get("TPU", 0)
    if found < chips and not allow_cpu:
        ray_tpu.shutdown()
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); the cluster on this "
            f"host advertises {found}.  Nothing is measured elsewhere.")
    return ray_tpu


def wait_gone(pid: int, timeout: float = 90.0) -> None:
    """The chip is free only once its holder has exited (a TPU worker
    takes seconds to die: PERF.md, PR 21)."""
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() > deadline:
            raise RuntimeError(f"pid {pid} still alive {timeout:.0f}s "
                               "after shutdown")
        time.sleep(0.05)


def driver_touched_backend() -> bool:
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()
