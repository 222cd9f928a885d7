"""The chip a run stands on: no fallback, and peaks from one table.

``require`` refuses a platform other than the TPU, fewer chips than the
cell asks for, and a ``device_kind`` that ``peaks.json`` does not list.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(SystemExit):
    """Raised, with a reason, where a run must not produce a result."""


def peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def check(devices, chips: int) -> dict:
    """The device stamp of ``devices`` (as ``jax.devices()`` gives them),
    with the peaks of their kind; raises :class:`NoChip` otherwise."""
    if not devices:
        raise NoChip("perfbench: JAX found no device")
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"perfbench: JAX found no TPU (platform "
                     f"{dev.platform!r}); refusing to fall back")
    if len(devices) < chips:
        raise NoChip(f"perfbench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    table = peaks()
    kind = dev.device_kind
    if kind not in table["kinds"]:
        raise NoChip(f"perfbench: device kind {kind!r} is not in "
                     f"peaks.json ({sorted(table['kinds'])})")
    return {"platform": dev.platform, "kind": kind, "count": chips,
            "peaks": table["kinds"][kind]}


def require(chips: int) -> dict:
    import jax
    return check(jax.devices(), chips)


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
