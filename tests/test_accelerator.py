"""Accelerator detection (parity: _private/accelerator.py TPU paths).

Detection never touches JAX: a chip belongs to one process, and the
process that counts chips (the driver, a node daemon) is not the one
that computes on them."""

import sys

import pytest

from ray_tpu.utils import accelerator as acc


def test_visible_chips_env_precedence(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2")
    assert acc.num_tpu_chips() == 3
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "")
    assert acc.num_tpu_chips() == 0     # isolation, not "unset"


def test_chip_count_comes_from_device_nodes(monkeypatch):
    """/dev/accel<N> on v4 and older hosts, /dev/vfio/<N> (never the
    /dev/vfio/vfio control node) on v5e and newer."""
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    nodes = {"/dev/accel[0-9]*": [],
             "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1"]}
    monkeypatch.setattr(acc.glob, "glob", lambda pat: nodes[pat])
    assert acc.num_tpu_chips() == 2
    nodes["/dev/accel[0-9]*"] = ["/dev/accel0", "/dev/accel1",
                                 "/dev/accel2", "/dev/accel3"]
    assert acc.num_tpu_chips() == 4


def test_version_from_environment_then_pci(monkeypatch, tmp_path):
    monkeypatch.delenv("RAYTPU_TPU_VERSION", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    assert acc.tpu_version() == acc.GOOGLE_TPU_V5E
    monkeypatch.setenv("RAYTPU_TPU_VERSION", "TPU-v5p")
    assert acc.tpu_version() == "TPU-v5p"
    # no environment: the PCI bus (vendor 0x1ae0, device 0x0063 = v5e)
    monkeypatch.delenv("RAYTPU_TPU_VERSION")
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE")
    dev = tmp_path / "0000:00:08.0"
    dev.mkdir()
    (dev / "vendor").write_text("0x1ae0\n")
    (dev / "device").write_text("0x0063\n")
    monkeypatch.setattr(acc.glob, "glob",
                        lambda pat: [str(dev / "vendor")])
    assert acc.tpu_version() == acc.GOOGLE_TPU_V5E
    (dev / "vendor").write_text("0x8086\n")
    assert acc.tpu_version() is None


def test_detection_imports_no_jax(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    monkeypatch.setitem(sys.modules, "jax", None)   # import jax -> error
    assert acc.node_resources_and_labels()[0]["TPU"] == 1.0


def test_node_resources_and_labels(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    monkeypatch.setenv("RAYTPU_TPU_VERSION", "TPU-v5p")
    monkeypatch.setenv("TPU_NAME", "my-pod")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    resources, labels = acc.node_resources_and_labels()
    assert resources["TPU"] == 4.0
    assert resources["TPU-v5p"] == 4.0
    assert resources["TPU-v5p-my-pod-head"] == 1.0  # slice-head resource
    assert labels["ici_index"] == "0"
    assert labels["raytpu.io/tpu-pod"] == "my-pod"

    # Non-zero worker: no head resource, ici_index reflects position.
    monkeypatch.setenv("TPU_WORKER_ID", "3")
    resources, labels = acc.node_resources_and_labels()
    assert "TPU-v5p-my-pod-head" not in resources
    assert labels["ici_index"] == "3"


def test_no_tpu_is_empty(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "")
    monkeypatch.delenv("TPU_NAME", raising=False)
    resources, labels = acc.node_resources_and_labels()
    assert resources == {} and labels == {}


def test_chip_worker_env():
    """Off the chip by default; pinned to the TPU backend when leased
    chips; bound to exactly its chips when the host has more."""
    assert acc.chip_worker_env(None, host_chips=4) == {
        "JAX_PLATFORMS": "cpu"}
    env = acc.chip_worker_env([1, 3], host_chips=4)
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == "1,3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    # every chip of the host: the host's own description stands
    assert acc.chip_worker_env([0], host_chips=1) == {
        "JAX_PLATFORMS": "tpu"}
    with pytest.raises(ValueError):
        acc.chip_worker_env([0, 1, 2], host_chips=4)


def test_chip_spec_is_one_table_and_unknown_is_an_error():
    v5e = acc.chip_spec("TPU v5 lite")
    assert v5e == acc.chip_spec(acc.GOOGLE_TPU_V5E)
    assert (v5e["peak_flops"], v5e["peak_int8_ops"],
            v5e["peak_hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    for kind in ("cpu", "TPU v999", ""):
        with pytest.raises(LookupError):
            acc.chip_spec(kind)
    with pytest.raises(LookupError):
        acc.local_chip_spec()            # this process computes on a CPU


def test_compile_cache_dir_is_fixed_and_placeable(monkeypatch):
    import jax

    path = acc.compile_cache_dir()
    assert path.endswith("/.jax_cache")
    assert path == acc.compile_cache_dir()          # no pid, no time
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert acc.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
