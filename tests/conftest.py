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


@pytest.fixture
def prefill_chunk(monkeypatch):
    """Set ``models/gpt.py PREFILL_CHUNK`` for one test: it is a module
    constant, not an option, so a test of a wave several chunks long at
    test size patches it down."""
    import importlib
    gpt = importlib.import_module("ray_tpu.models.gpt")
    return lambda positions: monkeypatch.setattr(gpt, "PREFILL_CHUNK",
                                                 positions)


def assert_chunked_wave_is_the_whole_wave(eng, lengths, bucket, chunk,
                                          set_chunk):
    """One prefill wave of ``len(lengths)`` prompts at ``bucket`` through
    ``eng``'s paged model, told the real lengths, once as ONE chunk (the
    whole span in one pass) and once in chunks of ``chunk``: the hidden
    states and the pool's rows at every real position and the first
    tokens agree; the pool is finite everywhere; and what no chunk
    computed (hidden states, pool rows) is exactly zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    wave, ps = len(lengths), eng.page_size
    rng = np.random.default_rng(sum(lengths))
    packed = np.zeros((wave, eng.packed_width(bucket)), np.int32)
    tables = np.zeros((wave, eng.max_pages), np.int32)
    for r, n in enumerate(lengths):
        packed[r, :n] = rng.integers(1, 256, n)
        packed[r, bucket] = n
        tables[r, :bucket // ps] = 1 + r * (bucket // ps) + np.arange(
            bucket // ps)
    packed, tables = jnp.asarray(packed), jnp.asarray(tables)
    positions = jnp.broadcast_to(jnp.arange(bucket), (wave, bucket))

    def run(positions_a_chunk):
        set_chunk(positions_a_chunk)
        eng._prefill_jit.clear()
        fresh = lambda: jax.tree.map(jnp.copy, eng._cache)  # noqa: E731
        hidden, mut = eng.model.apply(
            {"params": eng.params, "cache": fresh()}, packed[:, :bucket],
            positions, return_hidden=True, mutable=["cache"],
            block_tables=tables, lengths=packed[:, bucket])
        firsts, _ = eng._get_prefill_paged(bucket, wave)(
            eng.params, fresh(), packed, tables, jax.random.PRNGKey(0))
        (pool,) = [leaf for leaf in jax.tree.leaves(mut["cache"])
                   if eng._is_pool_leaf(leaf)]
        # [layers, pages, kv_heads, page, row] -> a row's positions in order
        rows = [np.asarray(pool[:, tables[r, :bucket // ps]]
                           .transpose(0, 1, 3, 2, 4)
                           .reshape(pool.shape[0], bucket, -1), np.float32)
                for r in range(wave)]
        return (np.asarray(hidden, np.float32), rows, np.asarray(firsts),
                np.asarray(pool, np.float32))

    whole, got = run(bucket), run(chunk)
    eng._prefill_jit.clear()
    assert np.isfinite(got[3]).all()
    np.testing.assert_array_equal(got[2], whole[2])
    computed = -(-max(lengths) // chunk) * chunk
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(got[0][r, :n], whole[0][r, :n],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(got[1][r][:, :n], whole[1][r][:, :n],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(got[0][r, computed:], 0)
        np.testing.assert_array_equal(got[1][r][:, computed:], 0)
    if computed < bucket:       # the one pass computed the right-pad too
        assert np.abs(whole[0][:, computed:]).max() > 0


def prefill_jaxpr(eng, bucket, wave, skip_pad):
    """The text of ``eng``'s prefill wave's forward at ``[wave,
    bucket]``, the model told the rows' real lengths (``skip_pad``, as
    ``engine_prefill`` tells it) or not (the one pass over the span that
    every prefill was)."""
    import jax
    import jax.numpy as jnp
    return str(jax.make_jaxpr(
        lambda params, cache, tokens, lens, tables: eng._last_logits(
            eng.model, params, cache, tokens,
            jnp.broadcast_to(jnp.arange(bucket), (wave, bucket)), lens,
            tables, skip_pad=skip_pad))(
        eng.params, eng._cache, jnp.zeros((wave, bucket), jnp.int32),
        jnp.ones((wave,), jnp.int32),
        jnp.zeros((wave, eng.max_pages), jnp.int32)))


def assert_only_several_chunks_loop(eng, set_chunk):
    """At ``[2, 32]``: with a chunk of 32 the forward told the lengths
    is the one pass, equation for equation; with a chunk of 8 it is
    another program, one that loops."""
    set_chunk(32)
    assert prefill_jaxpr(eng, 32, 2, True) == prefill_jaxpr(eng, 32, 2, False)
    set_chunk(8)
    told = prefill_jaxpr(eng, 32, 2, True)
    assert told != prefill_jaxpr(eng, 32, 2, False)
    assert "while" in told
