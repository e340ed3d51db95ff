"""The chip's published peaks (``bench/peaks.json``, keyed by
``device_kind``), for the readers of ``<kernel>_roofline`` and ``mfu``
metrics: each reader computes its kernel's least bytes or operations from
shapes and divides by these."""
from __future__ import annotations

import functools
import json
import pathlib

@functools.lru_cache(maxsize=None)
def _peaks() -> dict:
    return json.loads((pathlib.Path(__file__).parent / "peaks.json")
                      .read_text())


def peak(device_kind: str, key: str) -> float:
    """A published peak of ``device_kind``; an unknown device is an error."""
    devices = _peaks()["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device {device_kind!r} in "
                       "bench/peaks.json")
    return float(devices[device_kind][key])
