"""TPU accelerator manager: detection, slicing, pod topology.

Equivalent of the reference's TPUAcceleratorManager
(reference: python/ray/_private/accelerators/tpu.py, 398 LoC):
  - chip detection via /dev/accel* and vfio (:101-120) → detect_tpu_chips
  - GCE metadata / GKE env introspection (:52-72, 198-229)
  - TPU_VISIBLE_CHIPS + host-bounds plumbing for sub-host slicing
    (:157-196; valid chip counts {1,2,4} at :13,143-155)
  - per-pod custom resources `{tpu_name: 1, "TPU-<pod>-head": 1}` on
    worker 0 for pod-slice gang scheduling (:335-398)

Here pod-slice gangs are first-class placement-group bundles
(ray_tpu.util.placement_group.tpu_slice_bundles) instead of the head
resource hack, but the same per-node resources are still advertised for
compatibility.
"""
from __future__ import annotations

import os
import pathlib
import re
from typing import Dict, List, Optional

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_NAME_ENV = "TPU_NAME"
TPU_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5p-16"
TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"  # e.g. "2x2x2"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"

# chips one process may hold -> the bounds libtpu needs to lay them out
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
# single-host slice chip counts that can be sub-sliced (reference: tpu.py:13)
VALID_CHIP_COUNTS = tuple(_CHIP_BOUNDS)

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where chip processes keep JAX's persistent compilation cache:
    JAX_COMPILATION_CACHE_DIR when the machine sets it (no code sets
    another), else one fixed directory in the checkout — the path is
    part of the cache key, so it never carries a pid, time or temp name."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT / ".jax_cache")


GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"


def _gce_metadata(key: str) -> Optional[str]:
    """Best-effort GCE metadata read (reference: tpu.py:52-72). Zero-egress
    environments simply return None."""
    try:
        import urllib.request

        req = urllib.request.Request(
            GCE_METADATA_URL + key, headers={"Metadata-Flavor": "Google"}
        )
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        from ray_tpu._private.accelerator_detect import detect_tpu_chips

        return detect_tpu_chips()

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        accel = os.environ.get(TPU_TYPE_ENV) or _gce_metadata("accelerator-type")
        if accel:
            # "v5p-16" → "TPU-v5p"
            gen = accel.split("-")[0]
            return f"TPU-{gen}"
        return None

    @staticmethod
    def get_current_pod_type() -> Optional[str]:
        """Full pod type like 'v5p-16' (reference: tpu.py pod introspection)."""
        return os.environ.get(TPU_TYPE_ENV) or _gce_metadata("accelerator-type")

    @staticmethod
    def get_current_node_tpu_topology() -> Optional[str]:
        return os.environ.get(TPU_TOPOLOGY_ENV) or _gce_metadata("topology")

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def set_visible_accelerator_ids(ids: List[str]) -> None:
        """Restrict THIS process to a chip subset, before libtpu loads
        (reference: tpu.py:157-196). libtpu reads the chips it may open
        and the shape they form from the environment; the names are the
        ones JAX documents for several processes on one host, and the
        older aliases are dropped so a host-wide setting inherited from
        the machine cannot contradict the grant."""
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(ids)
        for legacy in ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
            os.environ.pop(legacy, None)
        bounds = _CHIP_BOUNDS.get(len(ids))
        if bounds is None:
            # no rectangle for this count: libtpu decides, and refuses
            # loudly at start-up what it cannot lay out
            os.environ.pop("TPU_CHIPS_PER_PROCESS_BOUNDS", None)
            os.environ.pop("TPU_PROCESS_BOUNDS", None)
        else:
            os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        if quantity != int(quantity):
            return False, "TPU request must be a whole number of chips"
        if int(quantity) not in VALID_CHIP_COUNTS and int(quantity) % 4 != 0:
            return (
                False,
                f"TPU request must be one of {VALID_CHIP_COUNTS} or a multiple of 4, got {quantity}",
            )
        return True, None

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Pod-slice gang resources (reference: tpu.py:335-398 — the pod
        name resource on every host and the `TPU-<pod>-head` resource on
        worker 0)."""
        out: Dict[str, float] = {}
        pod_name = os.environ.get(TPU_NAME_ENV) or _gce_metadata("instance-id")
        pod_type = TPUAcceleratorManager.get_current_pod_type()
        worker_id = os.environ.get(TPU_WORKER_ID_ENV, "0")
        if pod_name and pod_type:
            out[f"TPU-{pod_type}-pod-{pod_name}"] = 1.0
            if worker_id == "0":
                out[f"TPU-{pod_type}-head"] = 1.0
        return out


def infer_slice_shape(pod_type: str) -> Dict[str, int]:
    """Parse 'v5p-16' → {'gen': 'v5p', 'cores': 16, 'chips': 8, 'hosts': 2}.

    v4/v5p pods count TensorCores (2 per chip, 4 chips per host); v5e/v6e
    count chips directly (reference encodes the same vendor quirks in its
    pod-type handling, tpu.py:143-155).
    """
    m = re.match(r"(v\d+[a-z]*)-(\d+)", pod_type)
    if not m:
        raise ValueError(f"bad pod type {pod_type}")
    gen, n = m.group(1), int(m.group(2))
    if gen in ("v2", "v3", "v4", "v5p"):
        chips = max(n // 2, 1)
    else:  # v5e / v6e (litepod): number is chips
        chips = n
    hosts = max(chips // 4, 1)
    return {"gen": gen, "cores": n, "chips": chips, "hosts": hosts}
