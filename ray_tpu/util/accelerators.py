"""TPU accelerator detection (reference: python/ray/_private/accelerators/tpu.py).

Detects chips per host and slice metadata so the raylet can advertise
``TPU`` resources and slice labels (``TPUAcceleratorManager`` at tpu.py:267,
pod-type inference :151). A chip belongs to one process at a time, so the
raylet (a daemon that never exits) must learn the count WITHOUT opening a
chip: nothing here imports jax. Detection order:

1. explicit overrides (``RAY_TPU_CHIPS``, ``TPU_VISIBLE_CHIPS``),
2. the chips' device nodes — one ``/dev/accel<N>`` per chip under the accel
   driver, one ``/dev/vfio/<N>`` group per chip under vfio (what a v5e host
   exposes). This counts the chips actually attached to THIS machine,
3. ``TPU_ACCELERATOR_TYPE`` (GCE TPU-VM metadata). Last, because it names
   the slice the image was built for, not what is attached: a one-chip
   machine cut from a ``v5litepod-4`` image still says 4.

The remaining TPU_* variables only become labels.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Tuple

from ray_tpu._private.common import (
    LABEL_TPU_POD_TYPE,
    LABEL_TPU_SLICE,
    LABEL_TPU_TOPOLOGY,
    LABEL_TPU_WORKER_ID,
)


def _chips_for_accelerator_type(acc_type: str) -> int:
    """Chips on THIS host for a slice of the given type (e.g. 'v5litepod-16').

    v5e/v6e hosts have up to 4 chips (8 for v4/v5p with 4 dual-core chips);
    a host never has more chips than the slice total.
    """
    try:
        total = int(acc_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 0
    gen = acc_type.split("-")[0].lower()
    per_host = 4
    if gen in ("v2", "v3"):
        per_host = 8
    return min(total, per_host)


def _count_device_nodes(dev_root: str = "/dev") -> int:
    """Chips attached to this host, read off their device nodes (listing a
    directory opens no chip): ``accel<N>`` under the accel driver, else one
    numbered IOMMU group per chip under ``vfio/`` (the bare ``vfio/vfio``
    node is the container device, not a chip)."""
    return len(glob.glob(os.path.join(dev_root, "accel[0-9]*"))
               or glob.glob(os.path.join(dev_root, "vfio", "[0-9]*")))


def detect_tpu(dev_root: str = "/dev") -> Tuple[int, Dict[str, str]]:
    """Returns (num_chips_on_host, labels)."""
    labels: Dict[str, str] = {}
    env_chips = os.environ.get("RAY_TPU_CHIPS") or os.environ.get("TPU_VISIBLE_CHIPS")
    acc_type = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    slice_name = (
        os.environ.get("RAY_TPU_SLICE_NAME")
        or os.environ.get("TPU_NAME")
        or os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")[0]
    )
    worker_id = os.environ.get("TPU_WORKER_ID", "")
    topology = os.environ.get("TPU_TOPOLOGY", "")

    if env_chips:
        # a malformed override is an error, not "no TPU"
        chips = len(env_chips.split(",")) if "," in env_chips else int(env_chips)
    else:
        chips = _count_device_nodes(dev_root)
        if not chips and acc_type:
            chips = _chips_for_accelerator_type(acc_type)

    if chips:
        if slice_name:
            labels[LABEL_TPU_SLICE] = slice_name
        if acc_type:
            labels[LABEL_TPU_POD_TYPE] = acc_type
        if worker_id:
            labels[LABEL_TPU_WORKER_ID] = worker_id
        if topology:
            labels[LABEL_TPU_TOPOLOGY] = topology
    return chips, labels


def num_tpu_chips_on_host() -> int:
    return detect_tpu()[0]
