"""WorkerGroup: a gang of trainer actors, one per host.

Analog of /root/reference/python/ray/train/_internal/worker_group.py:92 and
backend_executor.py:42. Differences born of the TPU process model
(SURVEY.md §7 hard-part 4): exactly one process per host owns the chips, so
the group is placed with one bundle per host (STRICT_SPREAD on real pods)
and each worker is both "the" TPU process and the train-loop host.
"""

from __future__ import annotations

import os
import queue
import socket
import threading
import traceback
import uuid
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.air import session as air_session


class TrainWorker:
    """Actor body: runs the user train loop in a thread with an AIR session
    installed, and exposes a poll-based result channel to the driver."""

    def __init__(self, world_rank: int, world_size: int,
                 local_rank: int = 0, local_world_size: int = 1,
                 node_rank: int = 0):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.local_world_size = local_world_size
        self.node_rank = node_rank
        self._thread: Optional[threading.Thread] = None
        self._session: Optional[air_session._Session] = None
        self._final: Any = None
        self._error: Optional[str] = None
        self._done = threading.Event()

    # -- rendezvous helpers ------------------------------------------------
    def get_node_ip(self) -> str:
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.connect(("8.8.8.8", 80))
            ip = s.getsockname()[0]
            s.close()
            return ip
        except Exception:
            return "127.0.0.1"

    def find_free_port(self) -> int:
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def set_env(self, env: Dict[str, str]) -> None:
        os.environ.update(env)

    def setup_jax_distributed(self, coordinator: str) -> int:
        """Join the jax.distributed coordination service (multi-host). The
        TPU-native replacement for the reference's torch.distributed TCP
        rendezvous (train/torch/config.py:29). Returns local device count."""
        import jax
        # set_env may have changed JAX_PLATFORMS after jax was imported
        # in this process; jax reads the variable only at import, so the
        # choice goes through jax.config (no backend exists yet)
        plat = os.environ.get("JAX_PLATFORMS", "")
        if plat:
            jax.config.update("jax_platforms", plat)
        if plat.split(",")[0] == "cpu":
            # cross-process collectives on the CPU backend need an
            # explicit implementation; harmless when single-process
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        if self.world_size > 1:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=self.world_size,
                process_id=self.world_rank)
        return jax.local_device_count()

    def device_count(self) -> int:
        import jax
        return jax.device_count()

    def get_runtime_node_id(self) -> str:
        """The ray_tpu node hosting this rank: the driver's gang watch
        matches NODE_PREEMPTING/NODE_DEAD events against these ids
        (docs/fault_tolerance.md)."""
        try:
            from ray_tpu.runtime import core_worker as cw
            return cw.get_global_worker().node_id
        except Exception:
            return ""

    # -- host (DCN) collectives -------------------------------------------
    def init_host_collective(self, world_size: int,
                             group_name: str) -> None:
        """Join the gang's host-collective group (docs/collective.md):
        the DCN plane gradient sync / weight broadcast ride when the
        reduction isn't compiled into the step (cross-runtime workers,
        cross-slice sync).  The group name is exported so
        :func:`ray_tpu.train.sync_gradients` finds it from inside the
        user train loop."""
        from ray_tpu.util import collective as col
        col.init_collective_group(world_size, self.world_rank,
                                  group_name=group_name)
        os.environ["RAY_TPU_TRAIN_COLLECTIVE_GROUP"] = group_name

    def destroy_host_collective(self, group_name: str) -> None:
        from ray_tpu.util import collective as col
        try:
            col.destroy_collective_group(group_name)
        finally:
            os.environ.pop("RAY_TPU_TRAIN_COLLECTIVE_GROUP", None)

    def host_allreduce(self, arr, op: str = "sum", quantize=None):
        """Debug/test hook: one allreduce on the gang's host group."""
        from ray_tpu.util import collective as col
        return col.allreduce(
            arr, os.environ["RAY_TPU_TRAIN_COLLECTIVE_GROUP"], op,
            quantize=quantize)

    # -- train loop lifecycle ---------------------------------------------
    def start_training(self, train_fn: Callable, config: Dict[str, Any],
                       *, trial_name: str = "", trial_id: str = "",
                       trial_dir: str = "",
                       experiment_name: str = "",
                       checkpoint=None,
                       dataset_shard=None) -> None:
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("training already running on this worker")
        self._done.clear()
        self._error = None
        self._final = None
        shards = {"train": dataset_shard} if dataset_shard is not None else {}
        self._session = air_session.init_session(
            world_rank=self.world_rank, world_size=self.world_size,
            local_rank=self.local_rank,
            local_world_size=self.local_world_size,
            node_rank=self.node_rank,
            trial_name=trial_name, trial_id=trial_id, trial_dir=trial_dir,
            experiment_name=experiment_name,
            dataset_shards=shards, checkpoint=checkpoint)
        # init_session registered under THIS (actor RPC) thread; the runner
        # thread re-registers under its own id below — drop this entry so the
        # process holds exactly one session and get_session()'s any-thread
        # fallback works for user helper threads
        with air_session._session_lock:
            air_session._sessions.pop(threading.get_ident(), None)
        sess = self._session
        run_id = trial_id or uuid.uuid4().hex[:8]
        self._run_id = run_id

        def runner():
            from ray_tpu._private import step_stats
            with air_session._session_lock:
                air_session._sessions[threading.get_ident()] = sess
            try:
                run = self._start_step_stats(run_id, experiment_name)
            except Exception:
                run = None   # observability must never fail the loop
            try:
                takes_config = True
                try:
                    import inspect
                    takes_config = len(
                        inspect.signature(train_fn).parameters) > 0
                except (TypeError, ValueError):
                    pass
                self._final = train_fn(config) if takes_config else train_fn()
            except StopIteration:
                pass
            except BaseException:
                self._error = traceback.format_exc()
            finally:
                try:
                    step_stats.end_run(run)
                except Exception:
                    pass
                self._done.set()
                with air_session._session_lock:
                    air_session._sessions.pop(threading.get_ident(), None)

        self._thread = threading.Thread(target=runner, daemon=True,
                                        name=f"train_loop_r{self.world_rank}")
        self._thread.start()

    def _start_step_stats(self, run_id: str, experiment_name: str):
        """Open this rank's training-performance-plane run context
        (docs/observability.md): per-step phase clocks + goodput ledger,
        reports riding the worker's GCS client into the cluster step
        table.  The rank metadata (worker id + RPC address) lets
        ``ray-tpu profile --group`` gang-fan-out to every rank."""
        from ray_tpu._private import step_stats
        group = os.environ.get("RAY_TPU_TRAIN_COLLECTIVE_GROUP", "") \
            or experiment_name
        sink = None
        meta = {"world": self.world_size, "pid": os.getpid()}
        try:
            from ray_tpu.runtime import core_worker as cw
            worker = cw.get_global_worker()
        except Exception:
            worker = None
        if worker is not None:
            gcs = worker.gcs
            meta.update(worker_id=worker.worker_id.hex(),
                        node_id=worker.node_id,
                        address=list(worker.address))

            def sink(reports):
                gcs.call("report_step_stats", {"reports": reports},
                         timeout=5)
        return step_stats.start_run(
            run_id, group=group, rank=self.world_rank,
            world=self.world_size, sink=sink, meta=meta)

    def training_run_id(self) -> Optional[str]:
        return getattr(self, "_run_id", None)

    def next_result(self, timeout: float = 2.0):
        """Poll one reported (metrics, checkpoint) item, or status sentinels:
        ("done", final_return) / ("error", traceback) / ("timeout",)."""
        sess = self._session
        if sess is not None:
            item = sess.next_result(timeout=0 if self._done.is_set()
                                    else timeout)
            if item is not None:
                metrics, ckpt = item
                return ("result", metrics, ckpt)
        if self._done.is_set():
            if self._error is not None:
                return ("error", self._error)
            return ("done", self._final)
        return ("timeout",)

    def request_stop(self) -> None:
        if self._session is not None:
            self._session.stop_requested.set()
            # unblock a report() waiting for consumption
            self._session._consumed.set()

    def is_done(self) -> bool:
        return self._done.is_set()

    def health_check(self) -> bool:
        return True

    def shutdown_jax_distributed(self) -> None:
        try:
            import jax
            if self.world_size > 1:
                jax.distributed.shutdown()
        except Exception:
            pass


class WorkerGroup:
    """Driver-side handle to N TrainWorker actors placed one-per-bundle in a
    placement group."""

    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 placement_strategy: str = "PACK"):
        import ray_tpu
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import \
            PlacementGroupSchedulingStrategy

        import time as _time
        self.num_workers = num_workers
        self.created_ts = _time.time()   # gang-watch event horizon
        res = dict(resources_per_worker or {"CPU": 1.0})
        self.pg = placement_group([dict(res) for _ in range(num_workers)],
                                  strategy=placement_strategy)
        if not self.pg.wait(timeout_seconds=60):
            raise TimeoutError(
                f"placement group for {num_workers} train workers "
                f"({res}) not placed in 60s — cluster too small?")
        cpus = res.pop("CPU", 1.0)
        tpus = res.pop("TPU", 0.0)
        cls = ray_tpu.remote(num_cpus=cpus, num_tpus=tpus,
                             resources=res or None)(TrainWorker)
        self.workers = []
        for rank in range(num_workers):
            strategy = PlacementGroupSchedulingStrategy(
                placement_group=self.pg, placement_group_bundle_index=rank)
            self.workers.append(
                cls.options(scheduling_strategy=strategy).remote(
                    world_rank=rank, world_size=num_workers,
                    node_rank=rank))

    def execute(self, method: str, *args, **kwargs) -> List[Any]:
        """Call ``method`` on every worker, gather results in rank order."""
        import ray_tpu
        refs = [getattr(w, method).remote(*args, **kwargs)
                for w in self.workers]
        return ray_tpu.get(refs)

    def node_ids(self) -> List[str]:
        """ray_tpu node ids hosting the gang, in rank order (cached:
        the gang never migrates within one incarnation)."""
        if not getattr(self, "_node_ids", None):
            self._node_ids = self.execute("get_runtime_node_id")
        return list(self._node_ids)

    def execute_single(self, rank: int, method: str, *args, **kwargs) -> Any:
        import ray_tpu
        return ray_tpu.get(
            getattr(self.workers[rank], method).remote(*args, **kwargs))

    def shutdown(self) -> None:
        import ray_tpu
        from ray_tpu.util.placement_group import remove_placement_group
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self.pg)
        except Exception:
            pass
        self.workers = []
