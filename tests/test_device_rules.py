"""The rules that keep the EC tier from hiding the device (ISSUE 21).

- get_coder("auto") on a TPU backend is the Pallas coder and its failure
  propagates; the host chain is only for a backend that really is the CPU;
- the Pallas kernel interprets only when the caller says so;
- the persistent compile cache lives where JAX_COMPILATION_CACHE_DIR says,
  else at one fixed path in the checkout;
- ops/native rebuilds a library it did not build from this source on
  this host;
- the coder/device status surface initialises nothing.
"""

import inspect
import json
import os
import shutil
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

from seaweedfs_tpu.ec import coder as coder_mod
from seaweedfs_tpu.ec import get_coder
from seaweedfs_tpu.ops import native, rs_pallas
from seaweedfs_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fake_tpu(monkeypatch):
    """The backend reports "tpu" (the devices stay the CPU mesh) and the
    compile cache setting is restored afterwards."""
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])


# ------------------------------------------------------------ auto coder

def test_auto_on_tpu_propagates_device_coder_failure(fake_tpu, monkeypatch):
    def refuse(self, *a, **kw):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(coder_mod.PallasCoder, "__init__", refuse)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        get_coder("auto", 10, 4)


def test_auto_on_tpu_is_pallas_with_no_retry_ladder(fake_tpu):
    c = get_coder("auto", 10, 4)
    assert type(c) is coder_mod.PallasCoder
    assert not c.interpret
    # the devices are really the CPU: the compile refusal must surface
    # from the first encode, not turn into another tile or another coder
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        c.encode(np.zeros((10, 64), dtype=np.uint8))
    assert c.tile == rs_pallas.TILE


def test_auto_on_cpu_takes_the_host_chain(monkeypatch):
    assert type(get_coder("auto", 10, 4)).__name__ in ("CppCoder",
                                                       "JaxCoder")
    monkeypatch.setattr(native, "available", lambda: False)
    assert type(get_coder("auto", 10, 4)) is coder_mod.JaxCoder


def test_auto_refuses_a_cpu_that_is_a_failed_tpu(monkeypatch):
    real = jax.devices

    def devices(backend=None):
        if backend == "tpu":
            raise RuntimeError("Backend 'tpu' failed to initialize: "
                               "TPU is already in use by pid 7")
        return real(backend)

    monkeypatch.setattr(jax, "devices", devices)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        get_coder("auto", 10, 4)


def test_pallas_coder_needs_a_tpu_unless_interpreting():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        coder_mod.PallasCoder(10, 4)
    assert coder_mod.PallasCoder(10, 4, interpret=True).interpret


def test_formulation_pins_are_never_dropped(fake_tpu, monkeypatch):
    """A coder has one kernel, so there is no pin to drop: the names that
    selected an XLA formulation are unknown (the error lists what
    exists), and the old environment pin changes no coder."""
    for gone in ("jax_lut", "jax_xorsched"):
        with pytest.raises(KeyError) as e:
            get_coder(gone, 10, 4)
        for name in ("cpp", "jax", "mesh", "numpy", "pallas"):
            assert name in str(e.value), (gone, name)
    monkeypatch.setenv("WEED_EC_FORMULATION", "xorsched")
    assert get_coder("jax", 10, 4).describe()["formulation"] == "bitplane"
    c = get_coder("auto", 10, 4)
    assert isinstance(c, coder_mod.PallasCoder)
    assert "formulation" not in c.describe()


def test_sibling_shards_are_pinned_to_the_host_before_jax_loads():
    """The chip showed why the order matters: boot() imports jax, jax
    reads JAX_PLATFORMS once, and a fleet whose forked shard set the
    variable after those imports raced its parent for the chip."""
    from seaweedfs_tpu import cli
    src = inspect.getsource(cli.cmd_volume)
    pin = src.index('os.environ["JAX_PLATFORMS"] = "cpu"')
    assert pin < src.index("from .storage.store import Store")
    assert pin < src.index("from .ec.geometry import Geometry")
    assert 'JAX_PLATFORMS="cpu"' in inspect.getsource(cli.cmd_server)


# ------------------------------------------------------------- interpret

def test_pallas_interprets_only_when_told():
    sig = inspect.signature(rs_pallas.gf_apply_pallas)
    assert sig.parameters["interpret"].default is False
    src = inspect.getsource(rs_pallas)
    assert "default_backend" not in src
    assert src.count("vmem_limit_bytes=vmem_limit_bytes") == \
        src.count("pl.pallas_call(")
    data = np.zeros((10, 128), dtype=np.uint8)
    pm = np.ones((4, 10), dtype=np.uint8)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        rs_pallas.gf_apply_pallas(pm)(data)  # CPU backend, not asked
    assert rs_pallas.gf_apply_pallas(pm, interpret=True)(data).shape == \
        (4, 128)


# ---------------------------------------------------------- compile cache

def test_compile_cache_env_wins_and_code_sets_no_dir(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.configure() == str(tmp_path)
    # the only thing code may set is which programs are worth caching
    assert [a[0] for a in calls] in (
        [], ["jax_persistent_cache_min_compile_time_secs"])


def test_compile_cache_fixed_path_in_checkout(fake_tpu):
    assert compile_cache.configure() == compile_cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    assert compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_cpu_backend_places_none(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_no_cache_path_from_tempfile():
    """Neither the one place that sets the cache nor bench.py's phase
    runner builds a cache path from tempfile, a pid or the clock."""
    assert not {"tempfile", "time", "uuid"} & set(vars(compile_cache))
    assert "getpid" not in inspect.getsource(compile_cache.configure)
    with open(os.path.join(REPO, "bench.py")) as f:
        bench = f.read()
    assert "jax_cache" not in bench
    assert 'setdefault("JAX_COMPILATION_CACHE_DIR", ' \
        'compile_cache.CACHE_DIR)' in bench


# ----------------------------------------------------------- native stamp

def test_native_rebuilds_on_source_or_host_change(monkeypatch, tmp_path):
    ndir = tmp_path / "native"
    ndir.mkdir()
    for name in ("rs_core.cpp", "Makefile"):
        shutil.copy(os.path.join(native._NATIVE_DIR, name), ndir / name)
    so = str(ndir / "libseaweedtpu.so")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(ndir))
    monkeypatch.setattr(native, "_SO_PATH", so)
    monkeypatch.setattr(native, "_STAMP_PATH", so + ".stamp")
    builds = []
    real_run = subprocess.run

    def counting_run(cmd, **kw):
        builds.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native.subprocess, "run", counting_run)
    native._ensure_built()
    assert len(builds) == 1 and os.path.exists(so)
    native._ensure_built()
    assert len(builds) == 1  # same source, same host: accepted
    # a library that rode along from elsewhere: stamp absent or foreign
    os.remove(so + ".stamp")
    native._ensure_built()
    assert len(builds) == 2
    with open(ndir / "rs_core.cpp", "a") as f:
        f.write("\n// edited\n")
    native._ensure_built()
    assert len(builds) == 3  # source hash differs
    monkeypatch.setattr(native.platform, "machine", lambda: "other-cpu")
    native._ensure_built()
    assert len(builds) == 4  # host differs
    assert all("-B" in cmd for cmd in builds)


# ---------------------------------------------------------------- status

def test_status_names_coder_without_backend_init(tmp_path):
    """A store that has not encoded answers the status question without
    initialising any JAX backend; once a coder exists it is named with
    its device."""
    script = f"""
import json
import jax._src.xla_bridge as xb
from seaweedfs_tpu.parallel.mesh_coder import mesh_status
from seaweedfs_tpu.storage.store import Store
store = Store([{str(tmp_path)!r}], coder_name="jax")
st = mesh_status()
st["coder"] = store.coder_status()
assert not xb.backends_are_initialized(), "status initialised a backend"
store.coder()
print(json.dumps([st["coder"], store.coder_status()]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("WEED_EC_MESH_DEVICES", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    before, after = json.loads(out.stdout.splitlines()[-1])
    assert before == {"name": "jax", "resolved": []}
    assert after["resolved"][0]["coder"] == "JaxCoder"
    assert after["resolved"][0]["device"]["platform"] == "cpu"
    assert after["resolved"][0]["device"]["device_kind"]


def test_status_surface_over_http(monkeypatch):
    from seaweedfs_tpu.utils import metrics as metrics_mod
    from tests.cluster_util import Cluster

    # the "ec" registry is process-wide: a governed encode in an earlier
    # test leaves feed_mesh_devices behind, which reads as a live mesh
    metrics_mod.shared("ec").gauge("feed_mesh_devices", 0)
    monkeypatch.delenv("WEED_EC_MESH_DEVICES", raising=False)
    c = Cluster(n_volume_servers=1, coder_name="auto")
    try:
        vs = c.volume_servers[0]
        url = f"http://{vs.url}/admin/ec/mesh_status"

        def boom(*a, **kw):
            raise AssertionError("status touched the JAX backend")

        with monkeypatch.context() as m:
            m.setattr(jax, "devices", boom)
            m.setattr(jax, "default_backend", boom)
            with urllib.request.urlopen(url, timeout=10) as r:
                st = json.loads(r.read())
        assert st["coder"] == {"name": "auto", "resolved": []}
        assert st["devices"] is None
        vs.store.coder()
        with urllib.request.urlopen(url, timeout=10) as r:
            st = json.loads(r.read())
        desc = st["coder"]["resolved"][0]
        assert desc["coder"] in ("CppCoder", "JaxCoder")
        assert desc["geometry"] == "10+4"
    finally:
        c.shutdown()
