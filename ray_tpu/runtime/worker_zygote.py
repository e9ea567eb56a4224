"""Prefork worker zygote: fork warm worker processes in milliseconds.

Interpreter startup plus the runtime's imports cost every exec'd worker
a sizeable fraction of a second.  The reference amortizes worker
startup with a prestarted pool (worker_pool.cc); the zygote goes
further: ONE process per raylet pays the import cost, then every python
worker is an ``os.fork()`` away (~10 ms).

Chip ownership: every python worker of the node is a fork of this
process, so it must NEVER start a JAX backend — a zygote that held the
chip would pass a dead handle to every child and keep libtpu's lock
against all of them.  ``_handle_conn`` asserts this before each fork.

Mechanics:
  - The raylet launches ``python -m ray_tpu.runtime.worker_zygote
    --socket <path>`` once (eagerly, so it warms while the cluster
    boots) and sends framed spawn requests over the unix socket.
  - Each request is ONE fork: the parent replies with the child pid
    immediately (it knows it from fork()), and SIGCHLD is set to
    SIG_IGN so exited workers auto-reap — no zombies, no waitpid, no
    intermediate process.  (The first design double-forked so workers
    reparented to init; that cost two page-table copies of a jax-laden
    process plus a blocking waitpid PER SPAWN, serializing mass actor
    creation at ~80 ms/fork.  The worker resets SIGCHLD to SIG_DFL so
    user subprocess code sees normal child semantics.)
  - The worker child starts a new session, points stdio at its log
    files, swaps env/argv/config, closes inherited sockets, and calls
    ``worker_main.main()`` exactly as an exec'd worker would.

Workers that need a different interpreter (pip runtime envs) or
language (cpp) keep the exec path — the raylet falls back automatically.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import socket
import struct
import sys

_FRAME = struct.Struct("<I")


def send_msg(sock: socket.socket, obj) -> None:
    payload = pickle.dumps(obj)
    sock.sendall(_FRAME.pack(len(payload)) + payload)


def recv_msg(sock: socket.socket):
    head = _recv_exact(sock, _FRAME.size)
    if head is None:
        return None
    (n,) = _FRAME.unpack(head)
    body = _recv_exact(sock, n)
    return None if body is None else pickle.loads(body)


def _recv_exact(sock: socket.socket, n: int):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _become_worker(req: dict) -> None:
    """Runs in the forked child: turn this fork into a real worker."""
    import signal
    signal.signal(signal.SIGCHLD, signal.SIG_DFL)
    os.setsid()
    try:
        # forked children keep the zygote's cmdline in ps; at least fix
        # the comm name so `ps -C`/top distinguish workers from the
        # zygote (15-char kernel limit)
        with open("/proc/self/comm", "w") as f:
            f.write("ray_tpu_worker")
    except OSError:
        pass
    devnull = os.open(os.devnull, os.O_RDONLY)
    out = os.open(req["stdout"], os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                  0o644)
    err = os.open(req["stderr"], os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                  0o644)
    os.dup2(devnull, 0)
    os.dup2(out, 1)
    os.dup2(err, 2)
    for fd in (devnull, out, err):
        if fd > 2:
            os.close(fd)
    os.chdir(req["cwd"])
    os.environ.clear()
    os.environ.update(req["env"])
    # the zygote's CONFIG was resolved from ITS env; re-resolve from the
    # worker's blob (same raylet -> normally identical, but exact is free)
    from ray_tpu._private.config import CONFIG
    blob = req["env"].get("RAY_TPU_SYSTEM_CONFIG", "")
    try:
        CONFIG.set_overrides(json.loads(blob) if blob else {})
    except (ValueError, TypeError):
        pass
    # jax reads JAX_PLATFORMS once, when it is imported.  If something
    # the zygote imported pulled jax in, this fork inherited the ZYGOTE's
    # choice and the env update above came too late: re-pin it through
    # jax.config.  (XLA_FLAGS is read at first backend use, which the
    # env update covers.)  With jax not imported yet the variable alone
    # suffices, and importing jax here would only slow the spawn.
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms",
                          req["env"].get("JAX_PLATFORMS") or None)
    sys.argv = req["argv"]
    from ray_tpu.runtime import worker_main
    # os._exit (not sys.exit) everywhere: a forked worker must never run
    # the zygote's atexit/teardown.  But a crash has to be visible —
    # traceback to the redirected stderr (.err log) and a nonzero status.
    try:
        worker_main.main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (0 if e.code is None
                                                       else 1)
        if code != 0:
            import traceback
            traceback.print_exc()
            sys.stderr.flush()
        os._exit(code)
    except BaseException:
        import traceback
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    os._exit(0)


def _assert_no_backend() -> None:
    """The zygote must not hold the chip (module docstring)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb.backends_are_initialized():
        raise RuntimeError(
            "worker zygote has an initialized JAX backend "
            f"({sorted(xb._backends)}): every forked worker would "
            "inherit it, and on a TPU host none could get the chip")


def _handle_conn(conn: socket.socket, listener: socket.socket) -> None:
    while True:
        req = recv_msg(conn)
        if req is None:
            return
        _assert_no_backend()
        sys.stdout.flush()
        sys.stderr.flush()
        pid = os.fork()
        if pid == 0:
            listener.close()
            conn.close()
            _become_worker(req)         # never returns
            os._exit(1)
        send_msg(conn, {"pid": pid})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True)
    args = ap.parse_args()

    import signal as _signal

    # exited workers auto-reap (children of the zygote under the
    # single-fork protocol); _become_worker resets SIG_DFL in workers
    _signal.signal(_signal.SIGCHLD, _signal.SIG_IGN)
    # die with the raylet: a SIGKILLed raylet must not orphan a warm
    # jax-loaded process forever (PR_SET_PDEATHSIG is cleared on fork,
    # so spawned workers don't inherit the tie)
    try:
        import ctypes
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, _signal.SIGKILL)
        if os.getppid() == 1:          # raylet already gone
            return
    except OSError:
        pass

    # the expensive part, paid exactly once per raylet: the runtime
    from ray_tpu.runtime import worker_main       # noqa: F401

    try:
        os.unlink(args.socket)
    except FileNotFoundError:
        pass
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    listener.bind(args.socket)
    listener.listen(8)
    while True:
        conn, _ = listener.accept()
        try:
            _handle_conn(conn, listener)
        except OSError:
            pass
        finally:
            conn.close()


if __name__ == "__main__":
    main()
