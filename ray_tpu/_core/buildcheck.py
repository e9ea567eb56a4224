"""Staleness guard for the prebuilt native binaries in ray_tpu/_core/.

The repo ships built ELF artifacts (cpp_worker, libshmstore.so,
libscheduler.so, pycodec_tool) so a fresh checkout works without a
toolchain — but after any csrc/ edit a committed binary silently goes
stale and runtime behavior diverges from source.  `make -C csrc` writes
a stamp (`.src_sha256`, the hash of every csrc source) next to the
binaries; ensure_fresh() recomputes that hash and, on mismatch, rebuilds
before the binary is spawned/loaded.  A rebuild that is needed and fails
is an error: a binary that does not match its sources is never run.

Importable standalone (no package imports): the Makefile invokes
`python3 buildcheck.py --write-stamp` after a successful build.
"""
import hashlib
import os
import subprocess
import threading

_CORE_DIR = os.path.dirname(os.path.abspath(__file__))
_STAMP = os.path.join(_CORE_DIR, ".src_sha256")

_lock = threading.Lock()
_checked = False


def _csrc_dir() -> str:
    repo = os.path.dirname(os.path.dirname(_CORE_DIR))
    return os.path.join(repo, "csrc")


def source_hash():
    """Hash of every csrc source file, or None when the package is
    installed without its sources (nothing to be stale against)."""
    d = _csrc_dir()
    if not os.path.isdir(d):
        return None
    h = hashlib.sha256()
    for name in sorted(os.listdir(d)):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            h.update(name.encode())
            with open(os.path.join(d, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def write_stamp() -> None:
    digest = source_hash()
    if digest is not None:
        with open(_STAMP, "w") as f:
            f.write(digest)


def ensure_fresh(logger=None) -> None:
    """Verify the committed binaries match csrc/ sources; rebuild if not.

    Cheap (hashes ~15 small files) and runs at most once per process.
    Raises RuntimeError when the binaries are stale and the rebuild
    fails (toolchain missing, compile error).
    """
    global _checked
    with _lock:
        if _checked:
            return
        want = source_hash()
        if want is None or _stamp_matches(want):
            _checked = True
            return
        # Stale. Serialize the rebuild across PROCESSES too (several
        # raylets on one machine may spawn workers concurrently; two
        # parallel `make`s would race writing the same binaries).
        import fcntl
        lock_path = os.path.join(_CORE_DIR, ".build_lock")
        try:
            with open(lock_path, "w") as lockf:
                fcntl.flock(lockf, fcntl.LOCK_EX)
                # another process may have finished the rebuild while we
                # waited for the lock
                if not _stamp_matches(want):
                    if logger is not None:
                        logger.info("ray_tpu/_core binaries are stale "
                                    "relative to csrc/; rebuilding")
                    subprocess.run(["make", "-C", _csrc_dir()], check=True,
                                   capture_output=True, timeout=600)
                    write_stamp()
        except Exception as exc:  # toolchain missing / compile error
            detail = getattr(exc, "stderr", b"") or b""
            raise RuntimeError(
                "ray_tpu/_core binaries are stale relative to csrc/ "
                f"sources and the rebuild failed ({exc}); run "
                "`make -C csrc`\n"
                + detail.decode(errors="replace")[-2000:]) from exc
        _checked = True


_ARTEFACTS = ("libshmstore.so", "libscheduler.so", "pycodec_tool",
              "cpp_worker", "cpp_driver_demo")


def _stamp_matches(want: str) -> bool:
    """Stamp equals the source hash AND every artefact is present."""
    if not all(os.path.exists(os.path.join(_CORE_DIR, a))
               for a in _ARTEFACTS + (".src_sha256",)):
        return False
    with open(_STAMP) as f:
        return f.read().strip() == want


if __name__ == "__main__":
    import sys
    if "--write-stamp" in sys.argv:
        write_stamp()
    else:
        ensure_fresh()
