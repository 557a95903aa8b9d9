"""Real kernel FUSE mount e2e (the ctypes libfuse2 binding).

Runs only where /dev/fuse + libfuse + fusermount exist (this image has
all three). The mount runs as a subprocess; teardown lazy-unmounts.
"""

import ctypes.util
import os
import shutil
import subprocess
import sys
import time

import pytest

from cluster_util import Cluster

fuse_available = (os.path.exists("/dev/fuse")
                  and ctypes.util.find_library("fuse") is not None
                  and shutil.which("fusermount") is not None
                  and hasattr(os, "getuid") and os.getuid() == 0)

pytestmark = pytest.mark.skipif(not fuse_available,
                                reason="no usable /dev/fuse in this env")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE_FS = '''
import errno, os, stat, sys
from seaweedfs_tpu.mount.fuse_ctypes import fuse_main


class ProbeFS:
    """One-directory in-memory fs — just enough ops for a write/read
    round trip through the kernel."""

    def __init__(self):
        self.files = {}
        self._open_path = None

    def getattr(self, path):
        if path == "/":
            return {"mode": stat.S_IFDIR | 0o755, "nlink": 2}
        data = self.files.get(path)
        if data is None:
            raise OSError(errno.ENOENT, path)
        return {"mode": stat.S_IFREG | 0o644, "size": len(data)}

    def readdir(self, path):
        return [p[1:] for p in self.files]

    def create(self, path, mode):
        self.files[path] = b""
        self._open_path = path
        return 1

    def open(self, path, for_write=False):
        if path not in self.files:
            raise OSError(errno.ENOENT, path)
        self._open_path = path
        return 1

    def read(self, fh, size, offset):
        data = self.files[self._open_path]
        return data[offset:offset + size]

    def write(self, fh, data, offset):
        cur = self.files[self._open_path]
        if len(cur) < offset:
            cur += b"\\0" * (offset - len(cur))
        self.files[self._open_path] = (cur[:offset] + data
                                       + cur[offset + len(data):])
        return len(data)

    def truncate(self, path, length):
        self.files[path] = self.files.get(path, b"")[:length]

    def flush(self, fh):
        pass

    def release(self, fh):
        self._open_path = None


sys.exit(fuse_main(sys.argv[1], ProbeFS()))
'''

_fuse_functional_cache = None


def _require_functional_fuse(tmp_path):
    """The static prerequisites can all be present while the kernel's
    FUSE implementation is still partial: sandboxed kernels accept
    mount(2) and answer FUSE_INIT yet return ENOSYS on real file ops.
    Probe a trivial libfuse filesystem end-to-end (mount -> write ->
    read) and skip when the *environment* — not our mount code — is
    what's broken."""
    global _fuse_functional_cache
    if _fuse_functional_cache is None:
        _fuse_functional_cache = _probe_fuse(tmp_path)
    if not _fuse_functional_cache:
        pytest.skip("kernel FUSE is non-functional here (probe fs "
                    "mounted but file I/O failed — sandboxed kernel)")


def _probe_fuse(tmp_path) -> bool:
    mnt = tmp_path / "fuse_probe"
    mnt.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ":".join(
        p for p in (env.get("PYTHONPATH", ""), _REPO_ROOT) if p)
    proc = subprocess.Popen(
        [sys.executable, "-c", _PROBE_FS, str(mnt)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 15
        while time.time() < deadline and not os.path.ismount(mnt):
            if proc.poll() is not None:
                return False
            time.sleep(0.1)
        if not os.path.ismount(mnt):
            return False
        p = mnt / "probe.txt"
        p.write_bytes(b"ping")
        return p.read_bytes() == b"ping"
    except OSError:
        return False
    finally:
        subprocess.run(["fusermount", "-u", "-z", str(mnt)],
                       stderr=subprocess.DEVNULL)
        proc.terminate()
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_kernel_mount_end_to_end(tmp_path):
    _require_functional_fuse(tmp_path)
    c = Cluster(n_volume_servers=1)
    mnt = tmp_path / "mnt"
    mnt.mkdir()
    proc = None
    try:
        filer = c.add_filer(chunk_size=64 * 1024)
        time.sleep(0.3)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = ":".join(
            p for p in (env.get("PYTHONPATH", ""), _REPO_ROOT) if p)
        proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "mount",
             "-filer", filer.url, "-dir", str(mnt)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        deadline = time.time() + 20
        while time.time() < deadline:
            if os.path.ismount(mnt):
                break
            time.sleep(0.2)
        assert os.path.ismount(mnt), "mount never appeared"

        # kernel-path file operations
        p = mnt / "kernel.txt"
        p.write_bytes(b"written through the kernel")
        assert p.read_bytes() == b"written through the kernel"
        (mnt / "d").mkdir()
        big = os.urandom(300_000)
        (mnt / "d" / "big.bin").write_bytes(big)
        assert (mnt / "d" / "big.bin").read_bytes() == big
        assert sorted(os.listdir(mnt)) == ["d", "kernel.txt"]
        os.rename(mnt / "kernel.txt", mnt / "d" / "moved.txt")
        assert (mnt / "d" / "moved.txt").read_bytes() == \
            b"written through the kernel"
        os.setxattr(mnt / "d" / "moved.txt", "user.k", b"v")
        assert os.getxattr(mnt / "d" / "moved.txt", "user.k") == b"v"
        os.link(mnt / "d" / "moved.txt", mnt / "alias.txt")
        os.remove(mnt / "d" / "moved.txt")
        assert (mnt / "alias.txt").read_bytes() == \
            b"written through the kernel"

        # the data really lives in the filer, not the kernel cache
        import urllib.request
        with urllib.request.urlopen(
                f"http://{filer.url}/alias.txt", timeout=10) as r:
            assert r.read() == b"written through the kernel"
    finally:
        subprocess.run(["fusermount", "-u", "-z", str(mnt)],
                       stderr=subprocess.DEVNULL)
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        c.shutdown()
