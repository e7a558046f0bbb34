"""Band partitioning for the distributed condensed engine (§Perf 'banded').

Splits a symmetric single-layer condensed graph into ``n_shards``
contiguous virtual-node bands (for the fused 2-hop) and real-node bands
(for corrections), padding every band to equal length with inert entries
so the arrays shard evenly.  Consumed by the shard_map PageRank in
:mod:`repro.launch.cells` and by tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from .condensed import CondensedGraph

__all__ = ["BandedGraph", "band_partition"]


@dataclasses.dataclass
class BandedGraph:
    """Flat arrays whose equal n_shards-slices are per-band locals."""

    in_src: np.ndarray    # (S*eb,) global real ids
    in_dst: np.ndarray    # (S*eb,) band-local virtual ids
    out_src: np.ndarray   # (S*eb,) band-local virtual ids
    out_dst: np.ndarray   # (S*eb,) global real ids
    corr_src: np.ndarray  # (S*cb,) global real ids
    corr_dst: np.ndarray  # (S*cb,) band-local real ids
    corr_cnt: np.ndarray  # (S*cb,) float32 (0 = padding)
    deg: np.ndarray       # (n_real,) deduplicated out-degree
    n_real: int
    n_virtual: int
    n_shards: int

    @property
    def virt_band(self) -> int:
        return self.n_virtual // self.n_shards

    @property
    def real_band(self) -> int:
        return self.n_real // self.n_shards


def _pad_bands(values_per_band, fill, dtype):
    width = max(len(v) for v in values_per_band)
    out = np.full((len(values_per_band), width), fill, dtype=dtype)
    for i, v in enumerate(values_per_band):
        out[i, : len(v)] = v
    return out.reshape(-1)


def band_partition(
    graph: CondensedGraph,
    correction: Tuple[np.ndarray, np.ndarray, np.ndarray],
    n_shards: int,
    deg: np.ndarray,
) -> BandedGraph:
    if len(graph.chains) != 1 or graph.chains[0].n_layers != 1:
        raise ValueError("banding implemented for single-layer chains")
    chain = graph.chains[0]
    e_in, e_out = chain.edges
    n_real = -(-graph.n_real // n_shards) * n_shards
    n_virt = -(-e_in.n_dst // n_shards) * n_shards
    vb, rb = n_virt // n_shards, n_real // n_shards

    # group in-edges by virtual band; padding edge: src=0 -> local dst 0
    # is harmless only if it contributes 0 — use src pointing at a real
    # node and dst at a PADDED virtual id (>= e_in.n_dst) within the band.
    in_by_band = [[] for _ in range(n_shards)]
    for s, d in zip(e_in.src, e_in.dst):
        in_by_band[d // vb].append((s, d % vb))
    out_by_band = [[] for _ in range(n_shards)]
    for s, d in zip(e_out.src, e_out.dst):
        out_by_band[s // vb].append((s % vb, d))
    # Two dedicated inert virtual slots per band: in-edge padding WRITES
    # slot vb (which no out-edge reads), out-edge padding READS slot vb+1
    # (which no in-edge writes) — so padding moves zero mass.
    vb_pad = vb + 2
    in_bands = []
    out_bands = []
    for b in range(n_shards):
        in_bands.append([(s, d) for s, d in in_by_band[b]])
        out_bands.append([(s, d) for s, d in out_by_band[b]])
    width_in = max(len(v) for v in in_bands)
    width_out = max(len(v) for v in out_bands)
    width = max(width_in, width_out)
    in_src = np.zeros((n_shards, width), np.int32)
    in_dst = np.full((n_shards, width), vb, np.int32)      # write-only slot
    out_src = np.full((n_shards, width), vb + 1, np.int32)  # read-only slot
    out_dst = np.zeros((n_shards, width), np.int32)
    out_pad_mask = np.zeros((n_shards, width), bool)
    for b in range(n_shards):
        for i, (s, d) in enumerate(in_bands[b]):
            in_src[b, i], in_dst[b, i] = s, d
        for i, (s, d) in enumerate(out_bands[b]):
            out_src[b, i], out_dst[b, i] = s, d
            out_pad_mask[b, i] = True

    cs, cd, cm = correction
    c_by_band = [[] for _ in range(n_shards)]
    for s, d, m in zip(cs, cd, cm):
        c_by_band[d // rb].append((s, d % rb, m))
    cw = max(max((len(v) for v in c_by_band), default=1), 1)
    corr_src = np.zeros((n_shards, cw), np.int32)
    corr_dst = np.zeros((n_shards, cw), np.int32)
    corr_cnt = np.zeros((n_shards, cw), np.float32)
    for b in range(n_shards):
        for i, (s, d, m) in enumerate(c_by_band[b]):
            corr_src[b, i], corr_dst[b, i], corr_cnt[b, i] = s, d, m

    deg_pad = np.zeros(n_real, np.float32)
    deg_pad[: deg.size] = deg
    return BandedGraph(
        in_src=in_src.reshape(-1),
        in_dst=in_dst.reshape(-1),
        out_src=out_src.reshape(-1),
        out_dst=out_dst.reshape(-1),
        corr_src=corr_src.reshape(-1),
        corr_dst=corr_dst.reshape(-1),
        corr_cnt=corr_cnt.reshape(-1),
        deg=deg_pad,
        n_real=n_real,
        n_virtual=n_shards * vb_pad,
        n_shards=n_shards,
    )


def make_banded_pagerank(
    mesh,
    axes: Tuple[str, ...],
    n_real: int,
    n_virt_banded: int,     # n_shards * (vb_pad)
    n_shards: int,
    iters: int = 20,
    damping: float = 0.85,
):
    """shard_map PageRank over band-partitioned arrays (see BandedGraph).

    Per iteration: one all-gather of the rank vector + one psum-scatter of
    the partial result — no all-reduce (§Perf 'banded' variant).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    vb = n_virt_banded // n_shards
    rb = n_real // n_shards

    def pagerank_banded(args):
        def local(in_src, in_dst, out_src, out_dst, c_src, c_dst, c_cnt, deg):
            deg_loc = deg  # (rb,)

            def body(_, x_loc):
                contrib = jnp.where(
                    deg_loc > 0, x_loc / jnp.maximum(deg_loc, 1.0), 0.0
                )
                dangling = jax.lax.psum(
                    jnp.sum(jnp.where(deg_loc > 0, 0.0, x_loc)), axes
                )
                x_full = jax.lax.all_gather(contrib, axes, tiled=True)
                h_band = jax.ops.segment_sum(
                    jnp.take(x_full, in_src, axis=0), in_dst, num_segments=vb
                )
                y_partial = jax.ops.segment_sum(
                    jnp.take(h_band, out_src, axis=0), out_dst,
                    num_segments=n_real,
                )
                y_loc = jax.lax.psum_scatter(
                    y_partial, axes, scatter_dimension=0, tiled=True
                )
                corr = jax.ops.segment_sum(
                    jnp.take(x_full, c_src, axis=0) * c_cnt, c_dst,
                    num_segments=rb,
                )
                y_loc = y_loc - corr + dangling / n_real
                return (1.0 - damping) / n_real + damping * y_loc

            x0 = jnp.full((rb,), 1.0 / n_real, dtype=jnp.float32)
            x0 = jax.lax.pvary(x0, axes)
            return jax.lax.fori_loop(0, iters, body, x0)

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=tuple([P(axes)] * 8),
            out_specs=P(axes),
        )(
            args["in_src"], args["in_dst"], args["out_src"], args["out_dst"],
            args["corr_src"], args["corr_dst"], args["corr_cnt"], args["deg"],
        )

    return pagerank_banded
