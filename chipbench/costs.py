"""Operations and bytes the benchmark charges against the chip's peaks.

Model FLOPs come from each configuration's reference module
(``references/<reference>.py``, function ``flops``), which counts them
from the shapes.  The overlay kernel's bytes come from its page plan here,
not from how the kernel happens to move them, so a better kernel moves the
roofline share and not the count.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import numpy as np

DEVICES = Path(__file__).resolve().parent / "devices.json"

# page kinds of a restore's page plan (the JIF interval-table encoding)
KIND_ZERO, KIND_BASE, KIND_PRIVATE = 0, 1, 2


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(DEVICES.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {DEVICES.name}")
    return table[device_kind]


def overlay_bytes(kinds: np.ndarray, page_bytes: int) -> int:
    """Least HBM traffic of one overlay patch: every BASE page read once,
    every PRIVATE page read once from the uploaded pages, and every page of
    the tensor written once (ZERO pages are written, not read)."""
    kinds = np.asarray(kinds)
    n_base = int(np.count_nonzero(kinds == KIND_BASE))
    n_priv = int(np.count_nonzero(kinds == KIND_PRIVATE))
    return (n_base + n_priv + kinds.size) * int(page_bytes)
