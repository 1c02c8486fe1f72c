"""TPU accelerator detection, chip peaks, and chip ownership.

Parity: ray: python/ray/_private/accelerator.py:20-191 — TPU chip
count (device nodes or env), version (accelerator type), per-pod head
resources (``TPU-{version}-{pod}-head``), visibility isolation via
``TPU_VISIBLE_CHIPS``; constants in
python/ray/util/accelerators/accelerators.py (GOOGLE_TPU_V2/V3/V4).

A chip belongs to one process at a time, so detection never touches
JAX: ``ray_tpu.init()`` and every control process count chips from
device nodes and read the version from the environment or the PCI bus.
Only a process that was leased chips calls ``claim_tpu()``, which pins
JAX to the TPU backend and initialises it.  Topology labels feed
ICI-aware placement (SURVEY.md §7 phase 3: nodes carry slice/ICI
coordinates; bundle policies pack along them — see
runtime._reserve_bundles 'ici_index').
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

GOOGLE_TPU_V4 = "TPU-v4"
GOOGLE_TPU_V5E = "TPU-v5e"
GOOGLE_TPU_V5P = "TPU-v5p"
GOOGLE_TPU_V6E = "TPU-v6e"

# Published per-chip peaks (Google Cloud TPU documentation, the page of
# each version): dense bf16 FLOP/s, int8 OP/s, HBM bytes/s.  The one
# table every utilization figure in the repository divides by.
_CHIP_SPECS = {
    GOOGLE_TPU_V4: {"peak_flops": 275e12, "peak_int8_ops": 275e12,
                    "peak_hbm_bytes_per_s": 1228e9},
    GOOGLE_TPU_V5E: {"peak_flops": 197e12, "peak_int8_ops": 393e12,
                     "peak_hbm_bytes_per_s": 819e9},
    GOOGLE_TPU_V5P: {"peak_flops": 459e12, "peak_int8_ops": 918e12,
                     "peak_hbm_bytes_per_s": 2765e9},
    GOOGLE_TPU_V6E: {"peak_flops": 918e12, "peak_int8_ops": 1836e12,
                     "peak_hbm_bytes_per_s": 1640e9},
}

# jax ``device_kind`` (lower-cased) -> version.
_DEVICE_KINDS = {
    "tpu v4": GOOGLE_TPU_V4,
    "tpu v5 lite": GOOGLE_TPU_V5E,
    "tpu v5e": GOOGLE_TPU_V5E,
    "tpu v5": GOOGLE_TPU_V5P,
    "tpu v5p": GOOGLE_TPU_V5P,
    "tpu v6 lite": GOOGLE_TPU_V6E,
    "tpu v6e": GOOGLE_TPU_V6E,
}

# ``TPU_ACCELERATOR_TYPE`` prefix (before the "-<chips>") -> version.
_ACCELERATOR_TYPES = {
    "v4": GOOGLE_TPU_V4,
    "v5litepod": GOOGLE_TPU_V5E,
    "v5e": GOOGLE_TPU_V5E,
    "v5p": GOOGLE_TPU_V5P,
    "v6e": GOOGLE_TPU_V6E,
}

# PCI device id of a Google (vendor 0x1ae0) TPU -> version.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_PCI_DEVICE_IDS = {
    "0x005e": GOOGLE_TPU_V4,
    "0x0062": GOOGLE_TPU_V5P,
    "0x0063": GOOGLE_TPU_V5E,
    "0x006f": GOOGLE_TPU_V6E,
}


def chip_spec(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip: ``{"chip", "peak_flops", "peak_int8_ops",
    "peak_hbm_bytes_per_s"}`` for a jax ``device_kind`` ("TPU v5 lite")
    or a version string ("TPU-v5e").  A device that is not in the table
    is an error: a utilization against a made-up peak is worse than
    none."""
    version = _DEVICE_KINDS.get(device_kind.lower(), device_kind)
    spec = _CHIP_SPECS.get(version)
    if spec is None:
        raise LookupError(
            f"no peak rates for device {device_kind!r}; known: "
            f"{sorted(_DEVICE_KINDS)} / {sorted(_CHIP_SPECS)}")
    return {"chip": version, **spec}


def local_chip_spec() -> Dict[str, float]:
    """``chip_spec`` of the device THIS process computes on.  For
    processes that already hold a backend (an engine, a trainer, the
    benchmark); raises LookupError on a CPU backend."""
    import jax

    return chip_spec(jax.devices()[0].device_kind)


def num_tpu_chips() -> int:
    """Chips this host exposes (parity: accelerator.py chip count):
    ``TPU_VISIBLE_CHIPS`` when set, else the device nodes — one
    ``/dev/accel<N>`` per chip on v4 and older hosts, one
    ``/dev/vfio/<N>`` per chip on v5e and newer."""
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible is not None:
        # An empty value means "no chips visible" — isolation, not
        # unset; falling through would leak the host's full chip count.
        return len([c for c in visible.split(",") if c.strip()])
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def tpu_version() -> Optional[str]:
    """Resource-string TPU version (parity: GCE metadata
    accelerator-type): ``RAYTPU_TPU_VERSION``, else the TPU VM's
    ``TPU_ACCELERATOR_TYPE`` ("v5litepod-4"), else the PCI device id of
    the chips on the bus.  None when the host has no TPU."""
    env = os.environ.get("RAYTPU_TPU_VERSION")
    if env:
        return env
    acc_type = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    version = _ACCELERATOR_TYPES.get(acc_type.split("-")[0].lower())
    if version:
        return version
    for vendor_path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor_path) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor_path),
                                   "device")) as f:
                version = _PCI_DEVICE_IDS.get(f.read().strip())
        except OSError:
            continue
        if version:
            return version
    return None


def tpu_pod_name() -> Optional[str]:
    """Pod/slice identity from the TPU VM env (parity: TPU_NAME /
    the metadata instance attributes)."""
    return os.environ.get("TPU_NAME") or os.environ.get(
        "TPU_WORKER_HOSTNAMES"
    )


def tpu_worker_id() -> int:
    """This host's index inside the pod (parity: TPU_WORKER_ID)."""
    try:
        return int(os.environ.get("TPU_WORKER_ID", "0"))
    except ValueError:
        return 0


def node_resources_and_labels() -> (Dict[str, float], Dict[str, str]):
    """(extra resources, labels) a TPU host contributes at node start
    (parity: resource_spec.py merging accelerator resources; the
    ``TPU-{version}-{pod}-head`` resource on worker 0 is how the
    reference gang-schedules onto a slice head)."""
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    chips = num_tpu_chips()
    if chips <= 0:
        return resources, labels
    resources["TPU"] = float(chips)
    version = tpu_version()
    if version:
        resources[version] = float(chips)
        labels["raytpu.io/tpu-version"] = version
    pod = tpu_pod_name()
    worker_id = tpu_worker_id()
    labels["ici_index"] = str(worker_id)
    # 2-D host coordinate inside the slice, for ICI_CONTIGUOUS gang
    # placement.  TPU_TOPOLOGY (e.g. "4x4" chips) gives the host grid:
    # v4/v5p hosts own a 2x2x1 chip block, v5e/v6e hosts a 2x2; a
    # row-major host index maps onto (hosts_x, hosts_y).  Best-effort —
    # without topology info, a 1-D coordinate still gives contiguity
    # along one axis.
    topo = os.environ.get("TPU_TOPOLOGY", "")
    try:
        dims = [int(d) for d in topo.lower().split("x")]
        hosts_y = max(1, dims[1] // 2) if len(dims) >= 2 else 1
    except (ValueError, IndexError):
        hosts_y = 1
    labels["ici_coord"] = f"{worker_id // hosts_y},{worker_id % hosts_y}"
    if pod:
        labels["raytpu.io/tpu-pod"] = pod
        if worker_id == 0 and version:
            # Slice-head resource: exactly one per pod (parity:
            # accelerator.py:176-191 TPU-{version}-{pod}-head).
            resources[f"{version}-{pod}-head"] = 1.0
    return resources, labels


# -- chip ownership ----------------------------------------------------------

# Chips one process may hold, as the bounds libtpu wants for them.
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_worker_env(chip_ids: Optional[List[int]],
                    host_chips: int) -> Dict[str, str]:
    """Environment of a worker process.  ``chip_ids`` None keeps the
    worker off the chips (every control process and CPU task worker).
    A list pins JAX to the TPU backend, so a chip the worker cannot get
    is an error and never a quiet CPU run; a list shorter than the
    host's ``host_chips`` also binds the process to exactly those chips
    (parity: the reference sets TPU_VISIBLE_CHIPS the way it sets
    CUDA_VISIBLE_DEVICES), while a worker given every chip sees the
    host as the host's own environment describes it."""
    if chip_ids is None:
        return {"JAX_PLATFORMS": "cpu"}
    env = {"JAX_PLATFORMS": "tpu"}
    if len(chip_ids) < host_chips:
        if len(chip_ids) not in _PROCESS_BOUNDS:
            raise ValueError(
                f"a process can be bound to {sorted(_PROCESS_BOUNDS)} "
                f"chips, not {len(chip_ids)}")
        env.update({
            "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in chip_ids),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": _PROCESS_BOUNDS[len(chip_ids)],
            "TPU_PROCESS_BOUNDS": "1,1,1",
        })
    return env


def compile_cache_dir() -> str:
    """Where this checkout keeps JAX's persistent compilation cache when
    ``JAX_COMPILATION_CACHE_DIR`` does not place it: a fixed path inside
    the checkout, because the path is part of what makes a later run
    find the cache again."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX already reads it and nothing is set here."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def backend_initialised() -> bool:
    """Whether this process ever initialised a JAX backend (and so may
    hold a chip).  Asking JAX for its devices would initialise one, so
    anything that only wants to LOOK asks this first."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def claim_tpu() -> Dict[str, str]:
    """Make this process the owner of its chips: pin JAX to the TPU
    backend before first use, so a missing or busy chip raises instead
    of landing on the CPU, and initialise it.  Returns a report
    (platform, device_kind, count, library versions, cache directory)
    for the caller to print.  The start-up record's span
    ``runtime.claim_tpu``: the import of JAX, where this is the first,
    and the backend's initialisation."""
    from ray_tpu.util import tracing

    with tracing.span("runtime.claim_tpu", startup=True):
        import jax
        import jaxlib

        jax.config.update("jax_platforms", "tpu")
        cache = enable_compile_cache()
        devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"asked for the TPU backend, got {devices[0].platform!r}")
    try:
        from importlib.metadata import version as _pkg_version

        libtpu = _pkg_version("libtpu")
    except Exception:  # a libtpu that ships outside pip metadata
        libtpu = "unknown"
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache": cache,
    }
