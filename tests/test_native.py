"""Native data-plane server: build, serve, guard, interop with wire.py."""
import os

import pytest

from arrow_ballista_tpu import native
from arrow_ballista_tpu.net import wire
from arrow_ballista_tpu.net.wire import RemoteError


@pytest.fixture(scope="module")
def dp(tmp_path_factory):
    lib = native.dataplane()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    work = tmp_path_factory.mktemp("dpwork")
    (work / "job1" / "1" / "0").mkdir(parents=True)
    payload = b"arrow-ipc-bytes" * 1000
    (work / "job1" / "1" / "0" / "data-0.arrow").write_bytes(payload)
    port = lib.dp_start(str(work).encode(), 0, b"", 0)
    assert port > 0
    yield lib, str(work), port, payload
    lib.dp_stop()


def test_native_ping(dp):
    _, _, port, _ = dp
    payload, _ = wire.call("127.0.0.1", port, "ping")
    assert payload.get("native") is True


def test_native_fetch(dp):
    _, work, port, payload = dp
    path = os.path.join(work, "job1", "1", "0", "data-0.arrow")
    resp, data = wire.call("127.0.0.1", port, "fetch_partition", {"path": path})
    assert data == payload
    assert resp["num_bytes"] == len(payload)


def test_native_path_traversal_guard(dp):
    _, work, port, _ = dp
    for bad in [os.path.join(work, "..", "etc", "passwd"), "/etc/passwd",
                work]:  # the work dir itself is not a file under it
        with pytest.raises(RemoteError):
            wire.call("127.0.0.1", port, "fetch_partition", {"path": bad})


def test_native_missing_file(dp):
    _, work, port, _ = dp
    with pytest.raises(RemoteError):
        wire.call("127.0.0.1", port, "fetch_partition",
                  {"path": os.path.join(work, "job1", "1", "0", "nope.arrow")})


def test_native_bytes_served_counter(dp):
    lib, work, port, payload = dp
    before = lib.dp_bytes_served()
    path = os.path.join(work, "job1", "1", "0", "data-0.arrow")
    wire.call("127.0.0.1", port, "fetch_partition", {"path": path})
    assert lib.dp_bytes_served() >= before + len(payload)


def test_native_tsan_concurrent_fetch(tmp_path):
    """Race coverage (SURVEY §5): hammer the TSAN build of the data plane
    with concurrent fetches in a subprocess; any ThreadSanitizer report
    fails the test.  Skipped when the sanitizer toolchain is absent."""
    import subprocess
    import sys

    gcc = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                         capture_output=True, text=True)
    libtsan = gcc.stdout.strip()
    if gcc.returncode != 0 or "/" not in libtsan:
        pytest.skip("libtsan unavailable")
    build = subprocess.run(["make", "-C", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"),
        "sanitize"], capture_output=True, text=True)
    if build.returncode != 0:
        pytest.skip(f"sanitize build failed: {build.stderr[-500:]}")

    work = tmp_path / "w"
    (work / "j" / "1" / "0").mkdir(parents=True)
    (work / "j" / "1" / "0" / "data-0.arrow").write_bytes(b"x" * 65536)
    driver = r"""
import ctypes, os, sys, threading
sys.path.insert(0, {repo!r})
from arrow_ballista_tpu.net import wire
lib = ctypes.CDLL({so!r})
lib.dp_start.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
lib.dp_start.restype = ctypes.c_int
port = lib.dp_start({work!r}.encode(), 0, b"tok", 8)
assert port > 0
path = os.path.join({work!r}, "j", "1", "0", "data-0.arrow")
errs = []
def hammer():
    for _ in range(25):
        try:
            _, data = wire.call("127.0.0.1", port, "fetch_partition",
                                {{"path": path, "token": "tok"}})
            assert len(data) == 65536
        except Exception as e:
            errs.append(e)
ts = [threading.Thread(target=hammer) for _ in range(8)]
[t.start() for t in ts]; [t.join() for t in ts]
lib.dp_stop()
assert not errs, errs[:3]
print("TSAN_DRIVE_OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    so = os.path.join(repo, "native", "build", "libdataplane_tsan.so")
    env = dict(os.environ, LD_PRELOAD=libtsan,
               TSAN_OPTIONS="exitcode=66", JAX_PLATFORMS="cpu")
    code = driver.format(repo=repo, so=so, work=str(work))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert "WARNING: ThreadSanitizer" not in out, out[-4000:]
    assert proc.returncode == 0 and "TSAN_DRIVE_OK" in proc.stdout, out[-4000:]
