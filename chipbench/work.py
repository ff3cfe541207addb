"""The yardstick's tables: the chip's peaks and each kernel's work.

``peaks(device_kind)`` reads ``peaks.json``; a device kind it does not name
is an error, never a default.  ``kernel(name)`` loads ``kernels/<name>.py``:
the pattern of the kernel's module name in the device trace, the hook that
runs it, and the bytes one call needs.  ``roofline_share`` divides the least
time those bytes take at the peak HBM bandwidth by the kernel's device time.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from types import ModuleType
from typing import Optional

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str, here: pathlib.Path = HERE) -> dict:
    table = json.loads((here / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json names {sorted(table)}")
    return table[device_kind]


def load_module(path: pathlib.Path) -> ModuleType:
    """Import one file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernel(name: str, here: pathlib.Path = HERE) -> ModuleType:
    return load_module(here / "kernels" / f"{name}.py")


def roofline_share(record, name: str) -> Optional[float]:
    """Percent of the HBM roofline one kernel reached in the traced window:
    None where the window ran no call of it or the trace holds no module."""
    k = kernel(name, record.here)
    calls = record.hook_calls.get(k.HOOK, [])
    if record.trace is None or not calls:
        return None
    pattern = re.compile(k.MODULE)
    device_s = sum(sum(v) for n, v in record.trace.modules.items() if pattern.search(n))
    if device_s <= 0.0:
        return None
    least_s = sum(k.bytes_moved(c) for c in calls) / record.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
