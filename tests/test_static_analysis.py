"""raylint: the tier-1 gate plus red/green coverage per checker.

``test_tier1_gate_package_clean_and_fast`` IS the gate: it runs every
checker over the installed package and fails on any unallowlisted
violation, so a new violation anywhere in the tree fails the suite with
the checker's message — no new CI plumbing (docs/static_analysis.md).

The red/green tests build throwaway mini-packages (named ``ray_tpu`` so
the hardcoded plane/config module paths resolve) reproducing the
HISTORICAL bug each checker encodes — the inline-resolved-reply
deadlock (collective transport), the nested-``asyncio.run`` warmup bug,
the http_proxy executor-hop double-root, config-knob typos/rot, and
hot-path kill-switch reads — then assert the fixed shape passes.

The runtime sanitizers get direct unit coverage: a seeded A->B / B->A
lock inversion must raise naming BOTH acquisition sites, and the shm
ring protocol checker must catch a second writer and an out-of-order
ack on a real store segment.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

from ray_tpu._private.analysis import core
from ray_tpu._private.analysis.checkers import (async_hygiene,
                                                config_knobs,
                                                executor_context,
                                                inline_handlers,
                                                killswitch)


def _mk_index(tmp_path, files):
    """Write a throwaway package named ray_tpu and index it (pure AST —
    nothing is imported, so stubs don't need to work)."""
    root = tmp_path / "ray_tpu"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return core.ProjectIndex(str(root))


def _rules(violations):
    return sorted(v.rule for v in violations)


# --------------------------------------------------------------- the gate
def test_tier1_gate_package_clean_and_fast():
    """The whole package lints clean through the default baseline, and
    fast enough to ride tier-1 (<10s is the CLI contract; typical ~2s).
    Any new violation fails HERE with the checker's full message."""
    t0 = time.monotonic()
    violations = core.run_lint()
    dt = time.monotonic() - t0
    assert not violations, "raylint violations:\n" + "\n".join(
        v.render() for v in violations)
    assert dt < 10.0, f"lint took {dt:.1f}s (budget 10s)"


# ------------------------------------------------- inline-handler purity
def test_inline_handler_checker_catches_blocking_fast_method(tmp_path):
    """The PR 6 deadlock shape: a handler registered as a fast method
    resolves its reply through a wait (ServeBoard.wait_clear) — i.e.
    blocks the connection's reader thread."""
    idx = _mk_index(tmp_path, {"fastmod.py": '''
        import threading
        from ray_tpu._private import rpc

        class Board:
            def __init__(self):
                self._ev = threading.Event()

            def wait_clear(self):
                self._ev.wait(5.0)

        class Server:
            def __init__(self):
                self._board = Board()
                self._srv = rpc.Server(self._handle,
                                       fast_methods={"take"})

            def _handle(self, conn, method, payload):
                if method == "take":
                    return self._serve_take(payload)
                raise KeyError(method)

            def _serve_take(self, p):
                self._board.wait_clear()
                return p
    '''})
    vs = inline_handlers.check(idx)
    assert any(v.rule == "inline-handler-purity"
               and "take" in v.message and "wait" in v.message
               for v in vs), vs


def test_inline_handler_checker_passes_buffer_and_notify(tmp_path):
    """The sanctioned fast-handler shape: buffer + return a Deferred
    resolved elsewhere — nothing blocking on the reader."""
    idx = _mk_index(tmp_path, {"fastmod.py": '''
        from ray_tpu._private import rpc

        class Server:
            def __init__(self):
                self._buf = []
                self._srv = rpc.Server(self._handle,
                                       fast_methods={"take"})

            def _handle(self, conn, method, payload):
                if method == "take":
                    return self._serve_take(payload)
                raise KeyError(method)

            def _serve_take(self, p):
                d = rpc.Deferred()
                self._buf.append((p, d))
                return d
    '''})
    assert inline_handlers.check(idx) == []


def test_inline_handler_checker_predicate_registration(tmp_path):
    """Predicate-style fast_methods (worker_main's shape): every string
    the predicate compares against ``method`` counts as fast and must
    resolve to a handler."""
    idx = _mk_index(tmp_path, {"wm.py": '''
        import time
        from ray_tpu._private import rpc

        class W:
            def __init__(self):
                def fast(method, payload):
                    if method == "actor_task":
                        return True
                    return False
                self._srv = rpc.Server(self._handle, fast_methods=fast)

            def _handle(self, conn, method, p):
                if method == "actor_task":
                    return self._run_actor_task(p)
                raise KeyError(method)

            def _run_actor_task(self, p):
                time.sleep(0.5)
                return p
    '''})
    vs = inline_handlers.check(idx)
    assert any("actor_task" in v.message and "time.sleep" in v.message
               for v in vs), vs


# ------------------------------------------------------ async-def hygiene
def test_async_checker_catches_blocking_and_nested_loop(tmp_path):
    """The warmup incident: blocking sleep and asyncio.run inside an
    async def (both freeze/blow up the serving loop)."""
    idx = _mk_index(tmp_path, {"serve/replica.py": '''
        import asyncio
        import time

        class R:
            async def handle(self, req):
                time.sleep(0.1)
                asyncio.run(self._other())
                return req

            async def _other(self):
                return 1
    '''})
    vs = async_hygiene.check(idx)
    msgs = "\n".join(v.message for v in vs)
    assert len(vs) == 2 and "time.sleep" in msgs \
        and "nested event loop" in msgs, vs


def test_async_checker_passes_awaited_and_executor_shapes(tmp_path):
    """await asyncio.sleep / run_in_executor-shipped blocking work is
    the sanctioned pattern; a sync helper's sleep is not the loop's."""
    idx = _mk_index(tmp_path, {"serve/replica.py": '''
        import asyncio
        import time

        def _blocking_pull():
            time.sleep(0.1)

        class R:
            async def handle(self, req, loop):
                await asyncio.sleep(0.01)
                await loop.run_in_executor(None, _blocking_pull)
                return req
    '''})
    assert async_hygiene.check(idx) == []


# -------------------------------------------------- executor-hop context
_TRACING_STUB = '''
    def current_context():
        return None

    def bind_ctx(ctx, fn, *args, **kwargs):
        return fn
'''


def test_executor_hop_checker_catches_unbound_context_reader(tmp_path):
    """The http_proxy double-root bug: an executor hop (and a Thread)
    whose target reads the trace context without bind_ctx."""
    idx = _mk_index(tmp_path, {
        "util/tracing/tracing_helper.py": _TRACING_STUB,
        "serve/proxy.py": '''
        import threading
        from ray_tpu.util.tracing import tracing_helper

        class P:
            def _route(self):
                return tracing_helper.current_context()

            async def handle(self, loop):
                return await loop.run_in_executor(None, self._route)

            def spawn(self):
                threading.Thread(target=self._route).start()
    '''})
    vs = executor_context.check(idx)
    assert len(vs) == 2 and all(
        v.rule == "executor-hop-context" and "current_context" in v.message
        for v in vs), vs


def test_executor_hop_checker_passes_bind_ctx(tmp_path):
    idx = _mk_index(tmp_path, {
        "util/tracing/tracing_helper.py": _TRACING_STUB,
        "serve/proxy.py": '''
        from ray_tpu.util.tracing import tracing_helper

        class P:
            def _route(self):
                return tracing_helper.current_context()

            async def handle(self, loop, ctx):
                return await loop.run_in_executor(
                    None, tracing_helper.bind_ctx(ctx, self._route))
    '''})
    assert executor_context.check(idx) == []


# ------------------------------------------------------------ config-knob
_CONFIG_STUB = '''
    def _declare(name, type_, default, doc=""):
        pass

    _declare("used_knob", int, 1)
    _declare("dead_knob", int, 2)

    class Config:
        pass

    CONFIG = Config()
'''


def test_config_checker_catches_typo_and_dead_knob(tmp_path):
    idx = _mk_index(tmp_path, {
        "_private/config.py": _CONFIG_STUB,
        "user.py": '''
        from ray_tpu._private.config import CONFIG

        def f():
            return CONFIG.used_knob + CONFIG.hartbeat_ms
    '''})
    vs = config_knobs.check(idx)
    assert len(vs) == 2, vs
    typo = next(v for v in vs if "hartbeat_ms" in v.message)
    assert typo.symbol == "f" and "AttributeError" in typo.message
    dead = next(v for v in vs if "dead_knob" in v.message)
    assert dead.symbol == "dead_knob" and dead.path.endswith("config.py")


def test_config_checker_green_when_all_read_and_declared(tmp_path):
    idx = _mk_index(tmp_path, {
        "_private/config.py": _CONFIG_STUB,
        "user.py": '''
        from ray_tpu._private.config import CONFIG

        def f():
            return CONFIG.used_knob + getattr(CONFIG, "dead_knob")
    '''})
    assert config_knobs.check(idx) == []


# ------------------------------------------------------------ kill-switch
_RTM_STUB = '''
    def enabled():
        return True

    def counter(name, description=""):
        return None
'''


def test_killswitch_checker_catches_hot_read_and_dup_registration(
        tmp_path):
    idx = _mk_index(tmp_path, {
        "_private/runtime_metrics.py": _RTM_STUB,
        "a.py": '''
        from ray_tpu._private import runtime_metrics as rtm

        C1 = rtm.counter("ray_tpu_x_total", "x")

        def hot_path():
            if rtm.enabled():
                C1.inc()
    ''',
        "b.py": '''
        from ray_tpu._private import runtime_metrics as rtm

        C2 = rtm.counter("ray_tpu_x_total", "different description")
        D = rtm.counter("unprefixed_total", "bad namespace")
    '''})
    vs = killswitch.check(idx)
    msgs = "\n".join(v.message for v in vs)
    assert len(vs) == 3, vs
    assert "generation()-keyed" in msgs
    assert "registered more than once" in msgs
    assert "lacks the ray_tpu_ prefix" in msgs


def test_killswitch_checker_passes_generation_cache(tmp_path):
    idx = _mk_index(tmp_path, {
        "_private/runtime_metrics.py": _RTM_STUB,
        "_private/config.py": _CONFIG_STUB,
        "a.py": '''
        from ray_tpu._private import runtime_metrics as rtm
        from ray_tpu._private.config import CONFIG

        C1 = rtm.counter("ray_tpu_x_total", "x")
        _cache = (-1, False)

        def _on():
            global _cache
            gen = CONFIG.generation()
            if _cache[0] != gen:
                _cache = (gen, rtm.enabled())
            return _cache[1]

        def hot_path():
            if _on():
                C1.inc()
    '''})
    assert killswitch.check(idx) == []


# ------------------------------------------------- suppression machinery
def test_inline_disable_requires_justification(tmp_path):
    files = {"serve/r.py": '''
        import time

        class R:
            async def handle(self):
                time.sleep(0.1)  # raylint: disable=async-blocking
    '''}
    root = tmp_path / "a"
    idx = _mk_index(root, files)
    vs = core.run_lint(index=idx, baseline=None)
    assert _rules(vs) == ["allowlist-format"], vs

    files = {"serve/r.py": files["serve/r.py"].replace(
        "disable=async-blocking",
        "disable=async-blocking -- simulated think time in a test stub")}
    idx = _mk_index(tmp_path / "b", files)
    assert core.run_lint(index=idx, baseline=None) == []


def test_baseline_suppresses_and_stale_entries_fail(tmp_path):
    idx = _mk_index(tmp_path, {"serve/r.py": '''
        import time

        class R:
            async def handle(self):
                time.sleep(0.1)
    '''})
    raw = core.run_lint(index=idx, baseline=None)
    assert _rules(raw) == ["async-blocking"]
    key = raw[0].key

    baseline = tmp_path / "allow.txt"
    baseline.write_text(f"{key} -- stub think time, not a real loop\n")
    assert core.run_lint(index=idx, baseline=str(baseline)) == []

    # an entry without justification is itself a violation
    baseline.write_text(f"{key}\n")
    vs = core.run_lint(index=idx, baseline=str(baseline))
    assert "allowlist-format" in _rules(vs), vs

    # a stale entry (matching nothing) fails: the baseline only shrinks
    baseline.write_text(
        f"{key} -- stub think time, not a real loop\n"
        f"async-blocking ray_tpu/gone.py::R.handle -- was removed\n")
    vs = core.run_lint(index=idx, baseline=str(baseline))
    assert _rules(vs) == ["stale-allowlist"], vs

    # ...but only against a FULL run: under --rule filtering, other
    # rules' entries legitimately match nothing this pass
    vs = core.run_lint(index=idx, baseline=str(baseline),
                       rules=["config-knob"])
    assert vs == [], vs


# ------------------------------------------------- lock-order sanitizer
def test_lock_sanitizer_catches_seeded_inversion():
    """A->B then B->A across two lock classes raises at the SECOND
    acquisition pattern — no actual deadlock needed — and the report
    names both acquisition sites."""
    from ray_tpu._private.analysis import lock_sanitizer as ls
    ls.reset()
    try:
        a = ls._DebugLock("siteA.py:10")
        b = ls._DebugLock("siteB.py:20")
        with a:
            with b:      # records A -> B
                pass
        b.acquire()
        with pytest.raises(ls.LockOrderError) as ei:
            a.acquire()  # B -> A: inversion
        msg = str(ei.value)
        assert "siteA.py:10" in msg and "siteB.py:20" in msg, msg
        # both acquire windows are named (this test file's lines)
        assert msg.count("test_static_analysis.py") >= 2, msg
        b.release()
    finally:
        ls.reset()


def test_lock_sanitizer_rlock_condition_wait_stays_truthful():
    """Condition.wait on a wrapped RLock releases/re-acquires through
    the wrapper (recursion count preserved), so held-state survives the
    wait and nested with-blocks keep working."""
    import threading

    from ray_tpu._private.analysis import lock_sanitizer as ls
    ls.reset()
    try:
        lk = ls._DebugRLock("siteR.py:1")
        cv = threading.Condition(lk)
        hits = []

        def waiter():
            with cv:
                with lk:          # nested: recursion depth 2
                    pass
                cv.wait(5.0)
                hits.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.1)
        with cv:
            cv.notify_all()
        t.join(10)
        assert hits == ["woke"]
        assert not ls._held_snapshot(t.ident), "held-stack leaked"
    finally:
        ls.reset()


def test_lock_sanitizer_cross_thread_release_leaves_no_phantom():
    """A plain Lock acquired on thread A and released on thread B (the
    completion-gate pattern, legal for Lock) must drop A's stack entry
    — a phantom there would spray false order edges from everything A
    acquires afterwards."""
    import threading

    from ray_tpu._private.analysis import lock_sanitizer as ls
    ls.reset()
    try:
        gate = ls._DebugLock("siteGate.py:1")
        gate.acquire()
        releaser = threading.Thread(target=gate.release)
        releaser.start()
        releaser.join(10)
        assert not ls._held_snapshot(), \
            "cross-thread release left a phantom held entry"
        # and no bogus edges from the phantom
        other = ls._DebugLock("siteOther.py:2")
        with other:
            pass
        assert not any("siteGate" in a for a, _b in ls.edges()), \
            ls.edges()
    finally:
        ls.reset()


def test_lock_sanitizer_survives_gc_reentry_in_its_own_bookkeeping():
    """An allocation inside the sanitizer's bookkeeping can run the
    garbage collector, and a collected object's __del__ may take a
    sanitized lock ON THE SAME THREAD (ObjectRef.__del__ does).  The
    hooks must not re-enter their non-reentrant guards: that was a
    self-deadlock that hung a chaos test for the rest of a tier-1 run."""
    import threading

    from ray_tpu._private.analysis import lock_sanitizer as ls
    ls.reset()
    inner = ls._DebugLock("siteFinalizer.py:1")
    outer = ls._DebugLock("siteOuter.py:2")
    real_snapshot = ls._held_snapshot
    finished = threading.Event()

    def snapshot_with_finalizer(tid=None):
        # what the collector does mid-bookkeeping: a finalizer takes and
        # drops a sanitized lock while the guard is held
        with ls._held_guard:
            with inner:
                pass
        return real_snapshot(tid)

    def run():
        with outer:
            ls._held_snapshot = snapshot_with_finalizer
            try:
                with ls._DebugLock("siteNext.py:3"):
                    pass
            finally:
                ls._held_snapshot = real_snapshot
        finished.set()

    try:
        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert finished.wait(10), "sanitizer deadlocked on its own guard"
        assert ("siteOuter.py:2", "siteNext.py:3") in ls.edges()
        assert not any("siteFinalizer" in a or "siteFinalizer" in b
                       for a, b in ls.edges())
    finally:
        ls._held_snapshot = real_snapshot
        ls.reset()


def test_lock_sanitizer_install_gates_on_env_and_module(tmp_path,
                                                        monkeypatch):
    """install() wraps only locks created by instrumented files while
    the env gate is on; everything else gets real primitives."""
    import threading

    from ray_tpu._private.analysis import lock_sanitizer as ls
    old_prefixes = ls._prefixes
    ls.install()
    try:
        monkeypatch.setenv("RAY_TPU_DEBUG_LOCKS", "1")
        ls._prefixes = (str(tmp_path),)
        # a lock created from THIS (uninstrumented) file stays real
        assert not isinstance(threading.Lock(), ls._DebugLock)
        # code whose compile filename sits under the prefix is wrapped
        code = compile("import threading\nL = threading.Lock()\n",
                       str(tmp_path / "mod.py"), "exec")
        ns = {}
        exec(code, ns)
        assert isinstance(ns["L"], ls._DebugLock)
        # gate off: same site gets a real lock again
        monkeypatch.setenv("RAY_TPU_DEBUG_LOCKS", "0")
        ns2 = {}
        exec(code, ns2)
        assert not isinstance(ns2["L"], ls._DebugLock)
    finally:
        ls._prefixes = old_prefixes


# -------------------------------------------- channel protocol sanitizer
@pytest.fixture
def debug_channel_store(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_DEBUG_CHANNELS", "1")
    from ray_tpu.runtime.object_store import SharedMemoryStore
    store = SharedMemoryStore.create_segment(
        str(tmp_path / "chan_store"), 4 * 1024 * 1024)
    yield store
    store.close()
    store.unlink()


def test_channel_checker_catches_second_writer_and_bad_ack(
        debug_channel_store):
    from ray_tpu._private.analysis.channel_check import \
        ChannelProtocolError
    from ray_tpu.experimental.channel import (Channel, ChannelReader,
                                              ChannelWriter,
                                              channel_object_id)
    store = debug_channel_store
    ch = Channel.create(store, channel_object_id(b"debug-ring"),
                        nslots=4, nreaders=1, capacity=4096)
    assert ch._debug, "debug gate did not reach the channel"
    w, r = ChannelWriter(ch), ChannelReader(ch, 0)
    # normal traffic stays green around the ring (slot reuse included)
    for i in range(10):
        w.write(i)
        assert r.read(timeout=5.0) == i
    # a SECOND writer instance on the same ring trips the claim word
    w2 = ChannelWriter(ch)
    with pytest.raises(ChannelProtocolError, match="second writer"):
        w2.write("intruder")
    # out-of-order ack: consume two items zero-copy, ack the second
    w.write("x")
    w.write("y")
    _view1, _f1, ack1 = r.read_zc(timeout=5.0)
    _view2, _f2, ack2 = r.read_zc(timeout=5.0)
    with pytest.raises(ChannelProtocolError, match="out-of-order"):
        ack2()
    ack1()
    ack2()  # in order now: fine
    ch.close()


# ------------------------------------------------------------------- CLI
def test_cli_lint_clean_tree_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "lint"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_lint_violation_exits_nonzero(tmp_path):
    root = tmp_path / "ray_tpu"
    (root / "serve").mkdir(parents=True)
    (root / "serve" / "bad.py").write_text(textwrap.dedent('''
        import time

        async def handle():
            time.sleep(1)
    '''))
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", "lint",
         "--root", str(root)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "async-blocking" in proc.stdout
