"""Device identity, TPU-host detection, and the compile-cache location."""
import json
import os
import signal
import subprocess
import sys
import urllib.request

import jax
import pytest

from repro.hw import specs
from repro.hw.specs import TPU_V5E, chip_for_device, host_chip, stamp_chip, tpu_host

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_kind_lookup_names_the_chip():
    assert chip_for_device("tpu", "TPU v5 lite") is TPU_V5E


def test_unknown_tpu_kind_is_an_error():
    with pytest.raises(ValueError, match="TPU v99"):
        chip_for_device("tpu", "TPU v99")


def test_non_tpu_process_keeps_v5e_peaks_under_its_own_name():
    chip = chip_for_device("cpu", "cpu")
    assert chip.name == "cpu"
    assert chip.peak_flops_bf16 == TPU_V5E.peak_flops_bf16
    assert chip.hbm_bw == TPU_V5E.hbm_bw


def test_cpu_samples_are_not_stamped_as_tpu():
    from repro.dispatch import Dispatcher
    from repro.trace.session import artifact_meta

    here = jax.devices()[0].platform
    assert host_chip().name == here != "tpu_v5e"
    assert Dispatcher().chip.name == here
    assert artifact_meta()["chip"]["name"] == here  # this backend is up
    assert stamp_chip().name == here


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_stamping_without_a_device_starts_no_jax(tmp_path):
    """Processes that own no device stamp "host" and never import JAX: on a
    TPU host, starting a backend would take the chip."""
    script = f"""
import json, sys
from repro.fleet.cli import _default_key
from repro.trace import TraceCollector
from repro.trace.session import artifact_meta
from repro.trace.stream import StreamingSession
from repro.tune.cli import _env_key
from repro.tune.explore import Explorer, SweepSettings
from repro.dispatch.profiles import ProfileStore

StreamingSession({str(tmp_path / "s")!r}).attach(TraceCollector())
ex = Explorer(ProfileStore(), settings=SweepSettings(mode="synthetic"))
print(json.dumps({{
    "stream": json.load(open({str(tmp_path / "s" / "MANIFEST.json")!r}))["chip"]["name"],
    "artifact": artifact_meta()["chip"]["name"],
    "fleet": _default_key("sha", None)[1],
    "tune": _env_key()[1],
    "explorer": ex.chip.name,
    "jax": "jax" in sys.modules,
}}))
"""
    out = subprocess.run([sys.executable, "-c", script], env=_env(), cwd=REPO,
                         capture_output=True, text=True, timeout=120, check=True)
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec.pop("jax") is False
    assert set(rec.values()) == {"host"}


def _holds_jax(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as f:
        return "jaxlib" in f.read()


@pytest.mark.skipif(not os.path.exists("/proc/self/maps"), reason="needs /proc")
def test_router_front_door_and_synthetic_replica_start_no_jax(tmp_path):
    """A traced front door and its traced synthetic replica load no JAX, so
    neither takes a chip from the real replica of the same host."""
    from repro.utils.ready import read_ready_info, wait_for_ready_file

    ready = str(tmp_path / "router.ready")
    trace_dir = tmp_path / "trace"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.router", "--replicas", "1", "--synthetic",
         "--port", "0", "--ready-file", ready, "--workdir", str(tmp_path / "w"),
         "--trace-dir", str(trace_dir)],
        env=_env(), cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        wait_for_ready_file(ready, timeout_s=120, proc=proc)
        url = read_ready_info(ready)["url"]
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
            replica_pid = json.loads(resp.read())["replicas"]["r0"]["pid"]
        assert not _holds_jax(proc.pid)
        assert not _holds_jax(replica_pid)
        manifest = json.load(open(trace_dir / "MANIFEST.json"))
        assert manifest["chip"]["name"] == "host"
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_tpu_host_honours_jax_platforms(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu_host() is False


def test_tpu_host_reads_pci_ids(monkeypatch, tmp_path):
    def fake_device(name, vendor, device):
        d = tmp_path / name
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    real_glob = specs.glob.glob
    monkeypatch.setattr(specs.glob, "glob",
                        lambda pat: real_glob(str(tmp_path / "*" / "vendor")))
    fake_device("nic", "0x1ae0", "0x0042")  # a Google NIC is not a TPU
    assert tpu_host() is False
    fake_device("tpu", "0x1ae0", "0x0063")  # TPU v5e
    assert tpu_host() is True


def test_tpu_pci_ids_match_jax():
    """The copy of JAX's TPU PCI table, kept so a parent need not import JAX."""
    from jax._src import hardware_utils

    assert specs._GOOGLE_PCI_VENDOR == hardware_utils._GOOGLE_PCI_VENDOR_ID
    assert specs._TPU_PCI_DEVICES == set(hardware_utils._TPU_PCI_DEVICE_IDS)


def test_replica_manager_refuses_many_real_engines_on_a_tpu_host(monkeypatch,
                                                                 tmp_path):
    from repro.router import ReplicaManager

    monkeypatch.setattr(specs, "tpu_host", lambda: True)
    with pytest.raises(RuntimeError, match="one process per chip"):
        ReplicaManager(2, ["--arch", "qwen2-0.5b"], str(tmp_path))
    ReplicaManager(2, ["--synthetic"], str(tmp_path))  # no device: allowed
    ReplicaManager(1, ["--arch", "qwen2-0.5b"], str(tmp_path))


def test_tune_workers_refused_on_a_tpu_host(monkeypatch):
    from repro.core.events import EventLog
    from repro.dispatch.profiles import ProfileStore
    from repro.tune import explore

    monkeypatch.setattr(explore, "tpu_host", lambda: True)
    ex = explore.Explorer(ProfileStore(), log=EventLog(),
                          settings=explore.SweepSettings(mode="synthetic",
                                                         workers=2))
    with pytest.raises(RuntimeError, match="one process per chip"):
        ex.sweep(["rwkv6_scan"])


def test_compile_cache_defers_to_the_environment(monkeypatch):
    from repro.launch import cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    prev = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == prev  # nothing set


def test_compile_cache_sits_at_a_fixed_path_in_the_checkout(monkeypatch):
    from repro.launch import cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
