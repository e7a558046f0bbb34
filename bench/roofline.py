"""The least time the chip needs for a batch's work, from shape numbers.

Counts the work of personalized PageRank over a condensed graph with a
DEDUP-C correction, not of any one implementation of it, so a later
change of path (segment reduce, bitmap kernel, another correction form)
leaves the count as it is.  Per power iteration at batch width ``W``:

- every node row of every level of every chain (the real nodes, read on
  the way in and written on the way out, and each virtual layer, written
  and read once) at ``W`` float32 values;
- both int32 indices of every condensed edge, read once;
- every correction triple (two int32 indices and a float32 count), read
  once.

The work is bound by memory: a few operations per byte moved.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to {PEAKS}")
    return table[device_kind]


def ppr_bytes(shape: dict, width: int, iters: int) -> float:
    """Bytes one PPR batch of ``width`` columns must move."""
    rows = 2 * shape["n_real"] * len(shape["chains"])
    rows += 2 * sum(sum(c["virtual_layers"]) for c in shape["chains"])
    edges = sum(sum(c["edges"]) for c in shape["chains"])
    per_iter = 4 * width * rows + 8 * edges + 12 * shape["correction_triples"]
    return float(iters * per_iter)


def ppr_least_seconds(shape: dict, width: int, iters: int, peak: dict) -> float:
    return ppr_bytes(shape, width, iters) / peak["hbm_bytes_per_s"]
