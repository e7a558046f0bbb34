"""Autotuned kernel configurations + the measured-crossover dispatch table.

Two decisions used to be hardcoded: the Pallas kernel always streamed a
fixed ``(128, feature_block)`` source window, and ``'auto'`` dispatch
trusted the VMEM footprint formula alone — which routed cells to Pallas
at a measured 35x loss (BENCH_kernels.json, PR 3 smoke cells).  Following
the Vertica lesson (arXiv:1412.5263: measurement-driven planning beats
fixed heuristics), both become measured (DESIGN.md §6):

* :func:`autotune_spmm` sweeps ``CANDIDATES`` — (row_window,
  feature_block) pairs — against a layer's real packing and returns the
  fastest :class:`KernelConfig` plus the per-candidate timings.
* :func:`measure_crossover` races the winning Pallas configuration
  against the XLA segment path per (op, n_src-bucket, B-bucket) cell and
  records the result in a :class:`CrossoverTable` — a small frozen table
  carried by the pack (``PackedLayer.crossover`` /
  ``engine.PackedOperands.crossover``) and consulted by
  ``ops.resolve_backend`` / ``engine._kernel_applicable``, so ``'auto'``
  never again selects a backend the recording says is slower.

Buckets are power-of-two (``bit_length``) so a handful of measured cells
covers the whole size axis; lookups fall back to the nearest measured
bucket (deterministically) and, with no table at all, to the footprint
formula — packs that skip measurement behave exactly as before.

Everything here is host-side numpy/stdlib except the measurement
functions, which import the kernel wrappers lazily (this module is
imported by ``ops`` for the table types).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from .pack import TILE, fits_vmem

__all__ = [
    "KernelConfig",
    "DEFAULT_CONFIG",
    "CANDIDATES",
    "CrossoverEntry",
    "CrossoverTable",
    "src_bucket",
    "batch_bucket",
    "autotune_spmm",
    "measure_crossover",
]


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point of the autotune sweep: the streamed-window geometry.

    ``row_window`` — source rows fetched per streamed step (multiple of
    ``TILE``; wider windows amortize DMA issue over more resident rows).
    ``feature_block`` — width of one feature/batch tile (the outer grid
    axis walks the feature axis in these, so ``B ≫ 128`` frontiers
    stream through the same pipeline as a single tile).
    """

    row_window: int = TILE
    feature_block: int = 128

    def __post_init__(self) -> None:
        if self.row_window <= 0 or self.row_window % TILE:
            raise ValueError(
                f"row_window must be a positive multiple of {TILE}, "
                f"got {self.row_window}"
            )
        # feature_block only needs to tile the (padded) feature axis; the
        # legacy API allowed sub-TILE blocks, keep that working
        if self.feature_block <= 0:
            raise ValueError(
                f"feature_block must be positive, got {self.feature_block}"
            )


DEFAULT_CONFIG = KernelConfig()

# The sweep space.  Small on purpose: each candidate must be pinned by an
# exact-parity test (tests/test_kernels_autotune.py) before dispatch may
# select it, and the footprint formula must admit it at f32.
CANDIDATES: Tuple[KernelConfig, ...] = (
    KernelConfig(row_window=128, feature_block=128),
    KernelConfig(row_window=128, feature_block=256),
    KernelConfig(row_window=256, feature_block=128),
    KernelConfig(row_window=256, feature_block=256),
    KernelConfig(row_window=512, feature_block=128),
)


def src_bucket(n_src: int) -> int:
    """Power-of-two bucket of a source count: ``ceil(log2(n_src))``."""
    return max(int(n_src) - 1, 0).bit_length()


def batch_bucket(n_features: int) -> int:
    """Power-of-two bucket of a feature/batch width."""
    return max(int(n_features) - 1, 0).bit_length()


@dataclasses.dataclass(frozen=True)
class CrossoverEntry:
    """One measured cell: both backends' times and the winning config."""

    pallas_us: float
    xla_us: float
    row_window: int = TILE
    feature_block: int = 128

    @property
    def backend(self) -> str:
        return "pallas" if self.pallas_us <= self.xla_us else "xla"

    @property
    def config(self) -> KernelConfig:
        return KernelConfig(self.row_window, self.feature_block)


# (op, src_bucket, batch_bucket) — op is the semiring add_kind
Key = Tuple[str, int, int]


@dataclasses.dataclass(frozen=True)
class CrossoverTable:
    """Measured crossover decisions, frozen and hashable.

    Hashability matters: the table rides in ``PackedOperands`` /
    ``DevicePacked`` *meta* fields, which participate in jit static
    hashing — so entries are a sorted tuple of (key, entry) pairs, not a
    dict.  Use :meth:`from_entries` to build one.
    """

    entries: Tuple[Tuple[Key, CrossoverEntry], ...] = ()

    @classmethod
    def from_entries(cls, entries: Dict[Key, CrossoverEntry]) -> "CrossoverTable":
        return cls(entries=tuple(sorted(entries.items())))

    def __len__(self) -> int:
        return len(self.entries)

    def lookup(
        self, op: str, n_src: int, n_features: int
    ) -> Optional[CrossoverEntry]:
        """The entry for (op, n_src, B) — exact bucket, else the nearest
        measured bucket for the same op (deterministic: minimal bucket
        distance, ties broken by the sorted key order), else None."""
        if not self.entries:
            return None
        sb, bb = src_bucket(n_src), batch_bucket(n_features)
        best: Optional[Tuple[Tuple[int, int, int], CrossoverEntry]] = None
        for (eop, esb, ebb), entry in self.entries:
            if eop != op:
                continue
            rank = (abs(esb - sb) + abs(ebb - bb), esb, ebb)
            if best is None or rank < best[0]:
                best = (rank, entry)
        return None if best is None else best[1]

    def decide(self, op: str, n_src: int, n_features: int) -> Optional[str]:
        """'pallas' / 'xla' per the measurement, or None when unmeasured."""
        entry = self.lookup(op, n_src, n_features)
        return None if entry is None else entry.backend

    def config_for(
        self, op: str, n_src: int, n_features: int
    ) -> KernelConfig:
        """The measured-fastest kernel config for this cell (the default
        config when the op is unmeasured)."""
        entry = self.lookup(op, n_src, n_features)
        return DEFAULT_CONFIG if entry is None else entry.config

    # -- persistence (golden-tested: tests/test_crossover_golden.py) ----

    def to_json(self) -> str:
        cells = [
            {
                "op": op,
                "src_bucket": sb,
                "batch_bucket": bb,
                "pallas_us": e.pallas_us,
                "xla_us": e.xla_us,
                "row_window": e.row_window,
                "feature_block": e.feature_block,
            }
            for (op, sb, bb), e in self.entries
        ]
        return json.dumps({"version": 1, "cells": cells}, indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "CrossoverTable":
        doc = json.loads(text)
        if doc.get("version") != 1:
            raise ValueError(f"unknown crossover table version {doc.get('version')!r}")
        entries: Dict[Key, CrossoverEntry] = {}
        for c in doc["cells"]:
            key = (str(c["op"]), int(c["src_bucket"]), int(c["batch_bucket"]))
            entries[key] = CrossoverEntry(
                pallas_us=float(c["pallas_us"]),
                xla_us=float(c["xla_us"]),
                row_window=int(c["row_window"]),
                feature_block=int(c["feature_block"]),
            )
        return cls.from_entries(entries)


# -- measurement ------------------------------------------------------------

TimeFn = Callable[[Callable[[], object]], float]


def _op_semiring(op: str):
    """Representative semiring for a kernel op (add_kind)."""
    from ..core.semiring import MAX_TIMES, MIN_PLUS, PLUS_TIMES

    try:
        return {"sum": PLUS_TIMES, "min": MIN_PLUS, "max": MAX_TIMES}[op]
    except KeyError:
        raise ValueError(f"unknown kernel op {op!r}") from None


def _wall_time(fn: Callable[[], object], repeats: int = 3) -> float:
    """Best-of-N wall time in seconds, after one warmup (compile) call."""
    fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _viable(
    config: KernelConfig, n_features: int, itemsize: int, n_slots: int
) -> bool:
    return fits_vmem(
        n_features,
        config.feature_block,
        itemsize,
        n_slots=n_slots,
        row_window=config.row_window,
    )


def autotune_spmm(
    layer,
    n_features: int,
    op: str = "sum",
    candidates: Sequence[KernelConfig] = CANDIDATES,
    reverse: bool = False,
    interpret: Optional[bool] = None,
    time_fn: Optional[TimeFn] = None,
) -> Tuple[KernelConfig, Dict[KernelConfig, float]]:
    """Sweep ``candidates`` on a real packed layer; return (best, timings).

    Candidates whose working set exceeds the VMEM/SMEM budget are skipped
    (never timed, never selectable).  ``time_fn`` is injectable so tests
    can force deterministic 'measurements' without racing real kernels.
    """
    import jax.numpy as jnp

    from . import ops as _ops

    semiring = _op_semiring(op)
    bsb = layer.bsb_rev if reverse else layer.bsb
    if bsb is None:
        raise ValueError("autotune_spmm needs a packed direction")
    timer = time_fn or _wall_time
    x = jnp.ones((bsb.n_src, max(n_features, 1)), jnp.float32)
    timings: Dict[KernelConfig, float] = {}
    for cfg in candidates:
        if not _viable(cfg, n_features, x.dtype.itemsize, bsb.n_slots):
            continue

        def run(cfg=cfg):
            _ops.bitmap_spmm(
                layer,
                x,
                backend="pallas",
                feature_block=cfg.feature_block,
                interpret=interpret,
                semiring=semiring,
                reverse=reverse,
                config=cfg,
            ).block_until_ready()

        timings[cfg] = timer(run)
    if not timings:
        return DEFAULT_CONFIG, timings
    best = min(timings.items(), key=lambda kv: (kv[1], kv[0].row_window, kv[0].feature_block))
    return best[0], timings


def measure_crossover(
    layer,
    ops: Sequence[str] = ("sum",),
    batch_sizes: Sequence[int] = (128,),
    candidates: Sequence[KernelConfig] = CANDIDATES,
    interpret: Optional[bool] = None,
    time_fn: Optional[TimeFn] = None,
    xla: Optional[Callable[[object, object], object]] = None,
) -> CrossoverTable:
    """Race Pallas (autotuned per cell) against the XLA path and record
    the winners.  Called at pack time when measurement is requested
    (``PackedLayer.from_edges(..., measure=True)`` /
    ``engine.to_device_packed(..., measure=True)``).  ``xla(x, semiring)``
    is the XLA step that runs in the kernel's place; left None, the
    layer's segment path."""
    import jax.numpy as jnp

    from . import ops as _ops

    timer = time_fn or _wall_time
    entries: Dict[Key, CrossoverEntry] = {}
    for op in ops:
        semiring = _op_semiring(op)
        for b in batch_sizes:
            best_cfg, timings = autotune_spmm(
                layer,
                b,
                op=op,
                candidates=candidates,
                interpret=interpret,
                time_fn=time_fn,
            )
            x = jnp.ones((layer.n_src, b), jnp.float32)

            def run_xla():
                if xla is not None:
                    xla(x, semiring).block_until_ready()
                    return
                _ops.bitmap_spmm(
                    layer, x, backend="xla", semiring=semiring
                ).block_until_ready()

            t_xla = timer(run_xla)
            t_pallas = timings.get(best_cfg, float("inf"))
            key = (op, src_bucket(layer.n_src), batch_bucket(b))
            entries[key] = CrossoverEntry(
                pallas_us=t_pallas * 1e6,
                xla_us=t_xla * 1e6,
                row_window=best_cfg.row_window,
                feature_block=best_cfg.feature_block,
            )
    return CrossoverTable.from_entries(entries)
