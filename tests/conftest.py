"""Test environment: repo importable + 8 virtual CPU devices.

Must run before the first ``import jax`` anywhere in the test session so
the CPU backend is selected with 8 fake devices — this is how the
multi-chip ``shard_map``/``all_to_all`` path is exercised without a TPU
pod (SURVEY.md §4 item 4).  ``JAX_PLATFORMS=cpu`` is all it takes; the
persistent compile cache stays off so tests write nothing into the
checkout's ``.jax_cache`` (tests that check the cache turn it on).
"""

import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

FIXTURES = REPO_ROOT / "tests" / "fixtures"
REFERENCE = Path("/root/reference")


@pytest.fixture(scope="session")
def smoke_fixture():
    """Own 4-doc edge-case corpus with goldens generated from the compiled
    reference binary (tests/fixtures/smoke/)."""
    return FIXTURES / "smoke"


@pytest.fixture(scope="session")
def reference_dir():
    if not REFERENCE.is_dir():
        pytest.skip("/root/reference not mounted")
    return REFERENCE


def _socket_fds() -> set:
    """(fd, socket-inode) pairs currently open in this process — the
    leak unit for the daemon guard (inode comparison survives fd-number
    reuse)."""
    out = set()
    for entry in Path("/proc/self/fd").iterdir():
        try:
            target = os.readlink(entry)
        except OSError:
            continue  # raced with a close
        if target.startswith("socket:"):
            out.add((entry.name, target))
    return out


@pytest.fixture(autouse=True)
def _daemon_leak_guard(request):
    """Every ``daemon``-marked test must leave no stray sockets or
    background threads behind: a drained ServeDaemon joins every
    reader/writer/dispatcher/accept thread and closes every socket, so
    anything surviving the (grace-looped) check is a real leak."""
    if request.node.get_closest_marker("daemon") is None:
        yield
        return
    import threading
    import time

    before_threads = set(threading.enumerate())
    before_socks = _socket_fds()
    yield
    deadline = time.monotonic() + 5.0
    while True:
        leaked_threads = [t for t in threading.enumerate()
                          if t not in before_threads and t.is_alive()]
        leaked_socks = _socket_fds() - before_socks
        if not leaked_threads and not leaked_socks:
            return
        if time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert not leaked_threads, (
        f"daemon test leaked threads: {[t.name for t in leaked_threads]}")
    assert not leaked_socks, f"daemon test leaked sockets: {leaked_socks}"


def run_child(cmd, *, env=None, cwd=None, timeout=300):
    """Run a CLI child for crash/kill tests with a hang-proof guard.

    The child gets its own process group (``start_new_session``) so a
    timeout kills the WHOLE group with ``os.killpg`` — a wedged child
    (or anything it forked) can never outlive the test or hang the
    suite.  Returns the finished ``Popen`` (check ``.returncode``);
    a timeout is a test failure, not an exception up the stack.
    """
    import signal
    import subprocess

    proc = subprocess.Popen(cmd, cwd=cwd, env=env, start_new_session=True)
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        proc.wait()
        pytest.fail(
            f"child process hung past {timeout}s and was group-killed: "
            f"{' '.join(map(str, cmd[:6]))} ...")
    return proc


def read_letter_files(directory) -> bytes:
    """Concatenate a.txt..z.txt (the golden-diff unit, SURVEY.md §4)."""
    out = bytearray()
    for i in range(26):
        p = Path(directory) / f"{chr(ord('a') + i)}.txt"
        out += p.read_bytes()
    return bytes(out)
