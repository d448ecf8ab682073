"""TPU chip autodetection used by node bootstrap.

Equivalent of the reference's TPUAcceleratorManager detection path
(reference: python/ray/_private/accelerators/tpu.py:101-120 — counts
/dev/accel* and vfio devices). Kept in a tiny import-light module
because the raylet calls it at startup; it never imports JAX — the
process that asks how many chips a node has must not take one.
"""
from __future__ import annotations

import glob
import os
from typing import List


def tpu_device_nodes() -> List[str]:
    """The host's TPU device nodes: /dev/accel* (v2–v4 drivers) or the
    numbered vfio groups (v5e and later; /dev/vfio/vfio is the container
    node, not a chip)."""
    return sorted(glob.glob("/dev/accel*")) or sorted(glob.glob("/dev/vfio/[0-9]*"))


def detect_tpu_chips() -> int:
    env = os.environ.get("TPU_CHIPS", os.environ.get("RAY_TPU_CHIPS"))
    if env:
        return int(env)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    return len(tpu_device_nodes())
