"""Logical-axis sharding (MaxText-style rules, framework-local).

Models annotate tensors with *logical* axis names ("batch", "heads", ...).
A rules mapping (per arch config) resolves logical names to mesh axes.
Outside any mesh context the annotations are no-ops, so the same model
code runs in CPU smoke tests and 512-chip dry-runs.

Usage::

    with use_mesh_rules(mesh, cfg.sharding_rules):
        y = jax.jit(step, in_shardings=..., out_shardings=...)(...)

    # inside model code
    x = shard(x, "batch", "seq", "embed")
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "use_mesh_rules",
    "shard",
    "logical_spec",
    "named_sharding",
    "specs_for_tree",
    "current_mesh",
    "GRAPH_RULES",
    "shard_frontier",
    "edge_mesh",
    "shard_graph_edges",
    "extraction_shard_range",
    "merge_schedule",
    "MultihostSpillExtraction",
]

# Logical-axis rules for the condensed-graph engine (DESIGN.md §3/§5):
# frontier matrices are (graph_nodes, graph_batch); the *batch* axis is the
# data-parallel one — every device holds the full node axis (edge arrays
# are replicated or banded separately) and owns a slice of the sources.
# Activate with ``use_mesh_rules(mesh, GRAPH_RULES)`` around jitted calls.
GRAPH_RULES = {
    "graph_nodes": None,
    "graph_batch": ("data", "model"),
}

_state = threading.local()


def _ctx() -> Tuple[Optional[Mesh], Optional[Mapping]]:
    return getattr(_state, "mesh", None), getattr(_state, "rules", None)


@contextlib.contextmanager
def use_mesh_rules(mesh: Optional[Mesh], rules: Optional[Mapping]):
    """Activate a (mesh, logical-axis rules) context for :func:`shard` /
    :func:`logical_spec` calls in the dynamic scope (thread-local,
    re-entrant).  ``None`` for either disables annotations — the same
    model code then runs unconstrained (DESIGN.md §5)."""
    old = _ctx()
    _state.mesh, _state.rules = mesh, rules
    try:
        yield
    finally:
        _state.mesh, _state.rules = old


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost :func:`use_mesh_rules` context, if any."""
    return _ctx()[0]


def _resolve(axis: Optional[str], rules: Mapping, mesh: Mesh):
    """Logical axis -> mesh axis (or tuple), filtered to existing axes."""
    if axis is None:
        return None
    target = rules.get(axis, None)
    if target is None:
        return None
    if isinstance(target, (tuple, list)):
        present = tuple(t for t in target if t in mesh.axis_names)
        return present if present else None
    return target if target in mesh.axis_names else None


def logical_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Mapping] = None,
    mesh: Optional[Mesh] = None,
) -> PartitionSpec:
    """Resolve logical axis names to a ``PartitionSpec`` under the given
    (or ambient) rules + mesh; empty spec outside any context."""
    m, r = _ctx()
    mesh = mesh or m
    rules = rules or r
    if mesh is None or rules is None:
        return PartitionSpec()
    return PartitionSpec(*[_resolve(a, rules, mesh) for a in logical_axes])


def named_sharding(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Mapping] = None,
    mesh: Optional[Mesh] = None,
) -> Optional[NamedSharding]:
    """:func:`logical_spec` wrapped in a ``NamedSharding`` for
    ``jax.device_put`` / ``in_shardings``; ``None`` outside a context."""
    m, r = _ctx()
    mesh = mesh or m
    rules = rules or r
    if mesh is None or rules is None:
        return None
    return NamedSharding(mesh, logical_spec(logical_axes, rules, mesh))


def _dedup_axes(spec: PartitionSpec) -> PartitionSpec:
    """Drop later duplicate mesh-axis uses (keep-first priority): lets
    model code annotate e.g. ("batch", "act_seq", "vocab") and stay legal
    when an arch maps act_seq and vocab to the same mesh axis (SP)."""
    seen = set()
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in seen)
        seen.update(kept)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return PartitionSpec(*out)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply a sharding constraint if a mesh context is active (else no-op)."""
    mesh, rules = _ctx()
    if mesh is None or rules is None or len(mesh.devices.flatten()) == 1:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(
            f"rank {x.ndim} tensor got {len(logical_axes)} logical axes"
        )
    spec = _dedup_axes(logical_spec(logical_axes, rules, mesh))
    ns = NamedSharding(mesh, spec)
    return jax.lax.with_sharding_constraint(x, ns)


def shard_frontier(x: jax.Array) -> jax.Array:
    """Annotate a propagation frontier: ``(n,)`` vector or ``(n, B)`` batch.

    The same engine code then runs unconstrained on one CPU device and
    batch-sharded under ``use_mesh_rules(mesh, GRAPH_RULES)`` (rules may
    remap the logical names per deployment).  No-op outside a mesh context.
    """
    if x.ndim == 1:
        return shard(x, "graph_nodes")
    if x.ndim == 2:
        return shard(x, "graph_nodes", "graph_batch")
    raise ValueError(f"frontier must be (n,) or (n, B); got rank {x.ndim}")


def edge_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A mesh for edge-sharded propagation, with automatic axes.

    ``jax.make_mesh`` now returns explicit axes, under which the engine's
    gather from an edge-sharded index array has no unambiguous output
    sharding.  With automatic axes the compiler places that gather and
    the segment reduction after it, adding the collectives they need.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=auto, devices=devices
    )


def shard_graph_edges(graph, mesh: Mesh):
    """A :class:`~repro.core.engine.DeviceCondensed` with its edge arrays
    and the rows of each correction width class split across every
    device of ``mesh``.

    ``device_put`` needs divisible dims, so ragged edge lists are padded
    with *inert* entries: padded in-edges point real node 0 at a fresh
    dummy virtual node with no out-edges (and vice versa for out-edges),
    so no complete path, hence no propagated mass, is added.  Padded
    correction rows carry count 0; each node map, renumbered past them,
    is replicated.
    """
    import jax.numpy as jnp

    from ..core.correction_rows import CorrectionRows, DeviceCorrection
    from ..core.engine import DeviceBipartite, DeviceCondensed

    n_dev = mesh.devices.size
    spread = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))

    def padded(a, fill):
        pad = (-a.shape[0]) % n_dev
        if pad:
            fill = jnp.full((pad,) + a.shape[1:], fill, a.dtype)
            a = jnp.concatenate([a, fill])
        return jax.device_put(a, spread)

    chains = []
    for chain in graph.chains:
        layers = []
        for li, e in enumerate(chain):
            # grow every virtual level by 2 dummies: dummy A has only
            # in-edges, dummy B only out-edges -> no complete paths.
            first, last = li == 0, li == len(chain) - 1
            n_src = e.n_src + (0 if first else 2)
            n_dst = e.n_dst + (0 if last else 2)
            layers.append(DeviceBipartite(
                padded(e.src, 0 if first else e.n_src + 1),
                padded(e.dst, 0 if last else e.n_dst),
                n_src,
                n_dst,
            ))
        chains.append(tuple(layers))
    replicated = NamedSharding(mesh, PartitionSpec())

    def shard_rows(rows):
        sizes = np.array([0] + [i.shape[0] for i in rows.idx])
        grown = sizes + (-sizes) % n_dev
        # a node's row moves by the pad rows of the classes before its own
        old, new = np.cumsum(sizes), np.cumsum(grown)
        node_row = np.asarray(rows.node_row)
        cls = np.searchsorted(old[1:], node_row, side="right")
        node_row = node_row - old[cls] + new[cls]
        return CorrectionRows(
            tuple(padded(a, 0) for a in rows.idx),
            tuple(padded(a, 0) for a in rows.weight),
            jax.device_put(jnp.asarray(node_row, jnp.int32), replicated),
        )

    corr = None
    if graph.correction is not None:
        rev = graph.correction.rev
        corr = DeviceCorrection(
            shard_rows(graph.correction.fwd),
            None if rev is None else shard_rows(rev),
        )
    diag = graph.diag_mult
    return DeviceCondensed(
        chains=tuple(chains),
        direct=None,
        correction=corr,
        diag_mult=None if diag is None else jax.device_put(diag, replicated),
        n_real=graph.n_real,
        deduplicated=graph.deduplicated,
    )


def extraction_shard_range(
    n_shards: int,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> range:
    """The contiguous extraction-shard ids this host owns (DESIGN.md §8).

    The sharded extraction pipeline (``repro.core.extract``,
    ``n_shards=...``) is embarrassingly parallel across shards until the
    merge; this maps the global shard space onto JAX processes so each
    host runs ``extract``'s per-shard work — and its process-local
    pre-merge — for its own slice.  The division is ragged-safe in both
    directions: trailing hosts get one fewer shard when ``n_shards %
    process_count != 0``, and when ``n_shards < process_count`` the
    trailing hosts get *empty* ranges (they spill nothing, pre-merge
    nothing, and are simply absent from the cross-process reduce —
    :class:`MultihostSpillExtraction` schedules the tree over the
    processes with non-empty ranges only).  Ranges are contiguous and
    ascending in ``process_index``, which is what lets the pairwise
    reduce concatenate partner partials in shard order and stay
    byte-identical.  Single-process (the CPU test container): the full
    range.  ``process_index``/``process_count`` default to
    ``jax.process_index()``/``jax.process_count()``.
    """
    if process_index is None:
        process_index = jax.process_index()
    if process_count is None:
        process_count = jax.process_count()
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index {process_index} out of range [0, {process_count})"
        )
    base, extra = divmod(n_shards, process_count)
    lo = process_index * base + min(process_index, extra)
    hi = lo + base + (1 if process_index < extra else 0)
    return range(lo, hi)


def merge_schedule(n_partials: int) -> list:
    """Log-depth pairwise reduce schedule over ``n_partials`` contiguous
    partials (DESIGN.md §8).

    Returns a list of rounds; each round is a list of ``(dst, src)``
    index pairs, every pair independent within its round.  ``dst``
    absorbs ``src``, and — because partials are ordered by the contiguous
    shard ranges of :func:`extraction_shard_range` — ``src``'s
    accumulated shard range always directly follows ``dst``'s, so the
    merged partial is again a contiguous range and the final reduce at
    index 0 concatenates every shard in order (the byte-identity
    requirement).  Depth is ``ceil(log2(n_partials))``; a partial with no
    partner in a round carries to the next unchanged.
    """
    if n_partials < 0:
        raise ValueError(f"n_partials must be >= 0, got {n_partials}")
    rounds = []
    stride = 1
    while stride < n_partials:
        rounds.append([
            (i, i + stride)
            for i in range(0, n_partials, 2 * stride)
            if i + stride < n_partials
        ])
        stride *= 2
    return rounds


def _sync_barrier(process_count: int):
    """Default cross-phase barrier: no-op single-process, else
    ``jax.experimental.multihost_utils.sync_global_devices``."""

    def barrier(name: str) -> None:
        if process_count == 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)

    return barrier


class MultihostSpillExtraction:
    """Multi-host sharded extraction with spill-to-disk assembly and a
    log-depth cross-process tree-reduce merge (DESIGN.md §8).

    Every JAX process runs the same program against the same catalog and
    a *shared* spill directory (the exchange medium — spill records are
    how processes hand partials to each other, so no array ever crosses
    hosts in memory):

    1. :meth:`phase_nodes` — each process binds and spills node-space
       candidate records for its own shards
       (:func:`extraction_shard_range`).
    2. :meth:`phase_shards` — after a barrier, each process merges *all*
       node records into the (identical-everywhere) global ``NodeSpace``,
       extracts + spills its shard assemblies, and pre-merges them into
       one process partial (``partial_p<index>``).
    3. :meth:`phase_merge_round` — ``ceil(log2(P'))`` rounds of pairwise
       partial merges per :func:`merge_schedule`, over the ``P'``
       processes that own shards; one barrier per round.
    4. :meth:`phase_finish` — every process loads the root partial and
       builds the same ``CondensedGraph``; the root process finalizes the
       spill manifest (making the directory a valid
       :func:`repro.core.extract.merge_spilled_graph` input).

    :meth:`run` drives all phases with the default barrier
    (``multihost_utils.sync_global_devices`` when ``process_count > 1``,
    no-op single-process — the CPU fallback).  Tests drive the phases
    explicitly with simulated ``process_index``/``process_count`` and a
    no-op barrier, which is exactly equivalent because every
    cross-process data dependency goes through the spill directory at a
    phase boundary.

    The graph is byte-identical to ``extract(catalog, dsl_text)`` — the
    multi-host reduce is the same associative sorted-key-union merge,
    grouped differently.

    Use a *fresh* spill directory per multi-process run: the single-host
    pipeline clears a reused directory's stale records at start (it is
    the only writer), but with concurrent processes that wipe would race
    other processes' fresh records, so only the stale closing manifest is
    invalidated here — leftover records from an earlier differently-
    sharded run would be certified into the new manifest.
    """

    def __init__(
        self,
        catalog,
        dsl_text: str,
        n_shards: int,
        spill_dir: str,
        mode: str = "auto",
        preprocess: bool = False,
        max_resident_rows: Optional[int] = None,
        max_assembly_bytes: Optional[int] = None,
        merge_arity: int = 2,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        barrier=None,
    ) -> None:
        from repro.core.dsl import parse
        from repro.core.planner import ExtractionBudget
        from repro.core.serialize import ShardSpillStore

        self.catalog = catalog
        self.query = parse(dsl_text)
        self.n_shards = int(n_shards)
        self.mode = mode
        self.preprocess = preprocess
        self.merge_arity = int(merge_arity)
        self.process_index = (
            jax.process_index() if process_index is None else int(process_index)
        )
        self.process_count = (
            jax.process_count() if process_count is None else int(process_count)
        )
        self.my_shards = extraction_shard_range(
            self.n_shards, self.process_index, self.process_count
        )
        # processes that own shards: the partial owners the reduce runs over
        self.active = [
            p for p in range(self.process_count)
            if len(extraction_shard_range(self.n_shards, p, self.process_count))
        ]
        self.schedule = merge_schedule(len(self.active))
        self.root = self.active[0]
        self.barrier = barrier or _sync_barrier(self.process_count)
        self.budget = ExtractionBudget(
            max_resident_rows=max_resident_rows,
            max_assembly_bytes=max_assembly_bytes,
            spill_enabled=True,
        )
        self.store = ShardSpillStore(spill_dir)
        self.nodes = None
        self.props = None
        self._plans = None
        self._seconds = 0.0

    def _partial_name(self, process_index: int) -> str:
        return f"partial_p{process_index:05d}"

    # -- phases ---------------------------------------------------------------
    def phase_nodes(self) -> None:
        """Spill node-space candidate records for my shard range."""
        import time

        from repro.core.extract import _spill_node_shards

        t0 = time.perf_counter()
        _spill_node_shards(
            self.catalog, self.query.nodes_rules, self.n_shards,
            self.my_shards, self.store, self.budget,
        )
        self._seconds += time.perf_counter() - t0

    def phase_shards(self) -> None:
        """Global node space from all processes' records, then extract,
        spill, and pre-merge my shards into ``partial_p<me>``."""
        import time

        from repro.core.extract import (
            _node_space_from_spill,
            _plans_info,
            _spill_chain_shards,
            _write_nodespace_record,
        )
        from repro.core.serialize import tree_merge_records

        t0 = time.perf_counter()
        self.nodes, self.props = _node_space_from_spill(
            self.store, self.query.nodes_rules, self.n_shards, self.budget
        )
        self._plans = _plans_info(self.catalog, self.query, self.mode)
        names = _spill_chain_shards(
            self.catalog, self._plans, self.nodes, self.n_shards,
            self.my_shards, self.store, self.budget,
        )
        if names:
            reduced, _ = tree_merge_records(
                self.store, names, arity=self.merge_arity,
                out_prefix=f"pre_p{self.process_index:05d}_",
                budget=self.budget,
            )
            canonical = self._partial_name(self.process_index)
            if reduced != canonical:
                if reduced.startswith("pre_p"):
                    # an intermediate partial: just move it (no payload
                    # rewrite)
                    self.store.rename_record(reduced, canonical)
                else:
                    # a leaf shard record (single-shard slice): keep the
                    # leaf, copy it to the canonical partial name
                    assembly, _ = self.store.read_assembly(reduced)
                    self.store.write_assembly(canonical, assembly)
        if self.process_index == self.root:
            _write_nodespace_record(self.store, self.nodes, self.props)
        self._seconds += time.perf_counter() - t0

    def phase_merge_round(self, round_index: int) -> None:
        """Execute my pair (if any) of reduce round ``round_index``: load
        the partner's partial from the spill directory, merge it after
        mine, write the result back over my partial."""
        import time

        from repro.core.serialize import merge_assemblies

        t0 = time.perf_counter()
        for dst, src in self.schedule[round_index]:
            if self.active[dst] != self.process_index:
                continue
            mine, nb_dst = self.store.read_assembly(self._partial_name(self.active[dst]))
            theirs, nb_src = self.store.read_assembly(self._partial_name(self.active[src]))
            merged = merge_assemblies([mine, theirs])
            out_bytes = self.store.write_assembly(
                self._partial_name(self.active[dst]), merged
            )
            self.budget.note_merge(nb_dst + nb_src + out_bytes)
        self.budget.n_merge_rounds += 1
        self._seconds += time.perf_counter() - t0

    def phase_finish(self):
        """Load the root partial, finalize the manifest (root process
        only), and return the :class:`~repro.core.extract.ExtractionResult`
        — identical on every process."""
        import time

        from repro.core.extract import ExtractionResult, _graph_from_assembly

        t0 = time.perf_counter()
        merged, _ = self.store.read_assembly(self._partial_name(self.root))
        if self.process_index == self.root:
            self.store.finalize(meta={
                "kind": "extraction_spill",
                "n_shards": self.n_shards,
                "n_rules": len(self._plans or []),
                "mode": self.mode,
                "preprocess": self.preprocess,
                "final_record": self._partial_name(self.root),
                "process_count": self.process_count,
            })
        graph = _graph_from_assembly(
            self.nodes, self.props, merged, self.preprocess
        )
        self._seconds += time.perf_counter() - t0
        return ExtractionResult(
            graph=graph,
            nodes=self.nodes,
            plans=[p for p, _, _ in (self._plans or [])],
            seconds=self._seconds,
            dropped_endpoints=merged.dropped,
            mode=self.mode,
            n_shards=self.n_shards,
            budget=self.budget,
        )

    def run(self):
        """All phases with barriers between — the one-call multi-host
        entry point; single-process it degrades to the plain spilled
        pipeline (no barriers, full shard range)."""
        self.phase_nodes()
        self.barrier("spill:nodes")
        self.phase_shards()
        self.barrier("spill:shards")
        for r in range(len(self.schedule)):
            self.phase_merge_round(r)
            self.barrier(f"spill:merge{r}")
        return self.phase_finish()


def specs_for_tree(axes_tree, rules: Mapping, mesh: Mesh):
    """Pytree of logical-axis tuples -> pytree of NamedSharding."""
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, logical_spec(axes, rules, mesh)),
        axes_tree,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(isinstance(a, str) or a is None for a in v),
    )
