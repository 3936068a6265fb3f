"""The least work of one launch of a kernel, from shapes alone, and the
least time the chip could take for it.

`wave_kernel`: integer compares and segment sums over the resident cluster
snapshot, no matrix products; the published peak that bounds it is HBM
bandwidth. Whatever implements it has to read the snapshot once and write
its occupancy planes (`requested`, `nonzero_req`, `sel_counts`, `eterm_w`,
`port_counts`, `prio_req`) back once. The pod batch and the result are
left out (about a twentieth of the snapshot at 4,096 pods), which makes
the share a little low, never high. Bytes are logical (rows x columns x
item size), not the tiled layout the device pads them to, for the same
reason.

The shapes are `ops/encoding.DeviceSnapshot`'s at the capacities
`EncodingConfig.for_cluster(num_nodes)` chooses, copied here so that no
later PR can move the yardstick (PERF.md, Open questions, names the
original). Under a mesh the node axis is sharded, so each chip moves its
share of the node-major planes.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _pow2(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def capacities(num_nodes: int) -> dict:
    return {
        "N": _pow2(int(num_nodes * 1.25) + 1, 128),
        "K": 128, "R": 6, "TA": 8, "S": 64, "T": 64, "PV": 32, "I": 64,
        "AV": 16, "PB": 8,
    }


# field -> (dims, bytes per item, written back by a launch)
SNAPSHOT = {
    "valid": (("N",), 1, False),
    "unschedulable": (("N",), 1, False),
    "allocatable": (("N", "R"), 4, False),
    "requested": (("N", "R"), 4, True),
    "nonzero_req": (("N", "R"), 4, True),
    "label_vals": (("N", "K"), 4, False),
    "label_numvals": (("N", "K"), 4, False),
    "taint_key": (("N", "TA"), 4, False),
    "taint_val": (("N", "TA"), 4, False),
    "taint_effect": (("N", "TA"), 4, False),
    "sel_counts": (("N", "S"), 4, True),
    "eterm_w": (("N", "T"), 4, True),
    "eterm_topo_key": (("T",), 4, False),
    "eterm_kind": (("T",), 4, False),
    "port_counts": (("N", "PV"), 4, True),
    "image_bytes": (("N", "I"), 4, False),
    "avoid": (("N", "AV"), 1, False),
    "prio_req": (("N", "PB", "R"), 4, True),
    "band_prio": (("PB",), 4, False),
    "pdb_blocked": (("N", "PB"), 4, False),
    "cost_milli": (("N",), 4, False),
    "accel_class": (("N",), 4, False),
    "energy_milli": (("N",), 4, False),
}


def snapshot_bytes(num_nodes: int) -> dict:
    """{"read": bytes of the whole snapshot, "written": bytes of its
    occupancy planes} at the capacities of a cluster of num_nodes."""
    caps = capacities(num_nodes)
    read = written = 0
    for dims, item, back in SNAPSHOT.values():
        n = item
        for d in dims:
            n *= caps[d]
        read += n
        if back:
            written += n
    return {"read": read, "written": written}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmark/harness/peaks.json")
    return table[device_kind]


def least_seconds(work: str, config: dict, device_kind: str,
                  chips: int = 1):
    """The least time one launch of `work` could take on this device: the
    larger of operations / peak and bytes / peak. `wave_kernel` has no
    matrix product, so bytes over HBM bandwidth bounds it."""
    if work != "wave_kernel":
        raise KeyError(f"no work model for {work!r}")
    b = snapshot_bytes(config["nodes"]["count"])
    per_chip = (b["read"] + b["written"]) / max(1, chips)
    return per_chip / peaks(device_kind)["hbm_bytes_per_s"]
