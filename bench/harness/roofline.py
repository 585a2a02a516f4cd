"""Work of the slab scan, and the chip's peaks.

The scan scores a batch of ``B`` queries against every one of the ``N``
slots of the slab and keeps each query's best. Whatever kernel does it,
it needs ``2·B·N·d`` operations of a float GEMM (the queries are float32)
and must read each key once, ``N·d`` times the key's bytes, with the slot's
one-byte validity flag, besides the ``B·d`` float32 queries. A kernel that
reads more, or computes in more passes, takes longer; it does not change
this work.
"""
from __future__ import annotations

import json
from pathlib import Path

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def slab_scan_work(config: dict) -> tuple[float, float]:
    """(operations, bytes) of one scan of the whole slab by one batch."""
    b = int(config["batch_size"])
    n = int(config["capacity"])
    d = int(config["dim"])
    item = _ITEMSIZE[config["key_dtype"]]
    return 2.0 * b * n * d, float(n * d * item + n + b * d * 4)


def peaks(device_kind: str, table: Path) -> dict:
    """The peaks of one chip; a kind that is not in the table is an error."""
    devices = json.loads(table.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table}; known: {sorted(devices)}")
    return devices[device_kind]


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
