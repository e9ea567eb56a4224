"""``LLMServer`` plus what only the process that holds the chip can do
for the benchmark.  The serving path (``__call__``, ``stream``, the
engine) is inherited untouched; the methods added here are called before
and after the measured window, never inside a request.

Why a subclass and not ``build_app``: the benchmark needs the replica's
device memory peak, a ``jax.profiler`` trace from inside the replica, a
reference check against the engine's own weights, and a warm-up of
exactly the prefill shapes its traffic can use (``warmup_prompt_lens``
warms all six wave sizes of every bucket, and the largest of those do not
fit the chip at long prompts: PERF.md).  ``runners/serve.py`` deploys it
with ``serve.deployment`` exactly as ``build_app`` deploys ``LLMServer``.
"""

import collections
import time

from ray_tpu.serve.llm import LLMServer


def count_prefill_calls(eng) -> collections.Counter:
    """``{(bucket, wave): calls}``, counted from now on: the engine asks
    ``_get_prefill_paged`` for the program of every prefill wave it
    dispatches, so the pairs counted between two snapshots are the
    prefill programs that interval RAN (which have to be among those the
    cell warmed: ``prefill_pairs_used`` on the ``serve_done`` line)."""
    calls = collections.Counter()
    get = eng._get_prefill_paged

    def counting(bucket: int, wave: int):
        calls[bucket, wave] += 1
        return get(bucket, wave)
    eng._get_prefill_paged = counting
    return calls


class BenchLLMServer(LLMServer):

    def __init__(self, *args, **kwargs):
        from chipbench.lib import compile_watch
        compile_watch.snapshot()             # listeners on before any jit
        super().__init__(*args, **kwargs)
        self._prefill_calls = count_prefill_calls(self.engine)

    def bench_warm(self, pairs, concat_sizes) -> dict:
        """Compile (or load) the paged prefill program of each ``(bucket,
        wave)`` in ``pairs`` and the decode block program, the way
        ``LLMEngine.warmup`` does for all waves; then the small programs
        that join several waves' first tokens into one fetch."""
        import concurrent.futures
        import itertools

        import jax
        import jax.numpy as jnp
        import numpy as np
        eng = self.engine
        t0 = time.perf_counter()
        rng = jax.random.PRNGKey(0)
        for bucket, wave in pairs:
            packed = np.zeros((wave, bucket + 2), np.int32)
            packed[:, bucket] = 1
            tables = jnp.zeros((wave, eng.max_pages), jnp.int32)
            _, eng._cache = eng._get_prefill_paged(bucket, wave)(
                eng.params, eng._cache, jnp.asarray(packed), tables, rng)
        eng.warmup(prompt_lens=())           # the block program alone
        combos = [tuple(c) for c in concat_sizes.get("exact", [])] + [
            c for k, sizes in concat_sizes.get("products", [])
            for c in itertools.product(sizes, repeat=k)]

        def join(combo):
            np.asarray(jnp.concatenate(
                [jnp.zeros((w,), jnp.int32) for w in combo]))

        # tiny programs, never kept by the persistent cache: compile them
        # side by side (XLA releases the GIL)
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            list(pool.map(join, combos))
        return {"seconds": time.perf_counter() - t0, "programs": len(pairs),
                "joins": len(combos)}

    def bench_facts(self, since: dict = None) -> dict:
        import jax

        from chipbench.lib import compile_watch
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {"compiles": compile_watch.snapshot(),
                "longest_compile_s": (compile_watch.longest_since(since)
                                      if since else 0.0),
                "compiled_names": (compile_watch.names_since(since)
                                   if since else []),
                "memory_peak_bytes": max(
                    (s.get("peak_bytes_in_use", 0) for s in stats),
                    default=0),
                "prefill_calls": sorted(
                    [b, w, n] for (b, w), n in self._prefill_calls.items()),
                "load": self.engine.load_snapshot()}

    def bench_trace(self, action: str, trace_dir: str = "") -> float:
        """Returns the wall time at which tracing was on (start) or was
        still on (stop)."""
        if action == "start":
            from chipbench.lib import trace
            trace.start_trace(trace_dir)
            return time.time()
        import jax
        now = time.time()
        jax.profiler.stop_trace()
        return now

    def bench_reference(self, samples, rope_theta: float,
                        rms_norm_eps: float) -> list:
        """Teacher-forced reference check of greedy continuations the
        engine returned, against the engine's own (served) weights."""
        from chipbench.lib import reference
        weights = reference.from_program_params(self.engine.params)
        return [reference.greedy_margin(
            weights, s["prompt"], s["tokens"], rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps) for s in samples]
