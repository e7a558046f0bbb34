"""Cell builders: (arch, shape, mesh) -> (fn, ShapeDtypeStruct args, shardings).

One *cell* is an assigned (architecture x input-shape) pair.  The dry-run
jits ``fn`` with the returned in_shardings and lowers it against the
ShapeDtypeStructs — no arrays are ever allocated (the 40 full-size cells
would not fit on one host).

Step lowered per shape kind:
  train   -> train_step(state, batch)     (params + optimizer included)
  prefill -> prefill(params, tokens)      (serve dtype: bf16 params)
  decode  -> decode(params, cache, token)
  score_* -> sasrec scoring functions
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..configs import registry, shapes as shp
from ..configs.base import GNNConfig, RecsysConfig, TransformerConfig
from ..distributed.sharding import logical_spec, specs_for_tree, use_mesh_rules
from ..models import gnn, sasrec, transformer
from ..train import optimizer as opt_lib
from ..train import steps

__all__ = [
    "Cell", "build_cell", "all_cells",
    "ReplicaPlacement", "place_serving_replicas",
]

S = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Any
    rules: Dict
    cfg: Any
    flops_note: str = ""
    donate: Tuple[int, ...] = ()   # donated arg indices (state / KV cache)


def _ns(mesh, rules, axes):
    from ..distributed.sharding import _dedup_axes

    # keep-first duplicate resolution (e.g. cache_seq and kv_heads both on
    # 'model' for MHA-style archs: the seq dim wins, heads replicate)
    return NamedSharding(mesh, _dedup_axes(logical_spec(axes, rules, mesh)))


def _replicated_tree(tree, mesh):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, PartitionSpec()), tree
    )


def _opt_shardings(opt_struct, param_specs, mesh):
    """Optimizer-state shardings derived from param shardings.

    adamw/sgdm moments mirror params; adafactor's factored r/c drop the
    last / second-to-last axis of the param spec.
    """
    def factored(spec_tree, leaf_dict):
        spec = spec_tree.spec if isinstance(spec_tree, NamedSharding) else spec_tree
        out = {}
        for k in leaf_dict:
            if k == "v":
                out[k] = NamedSharding(mesh, PartitionSpec(*spec))
            elif k == "r":
                out[k] = NamedSharding(mesh, PartitionSpec(*spec[:-1]))
            elif k == "c":
                out[k] = NamedSharding(
                    mesh, PartitionSpec(*(tuple(spec[:-2]) + tuple(spec[-1:])))
                )
        return out

    out = {}
    for key, sub in opt_struct.items():
        if key in ("m", "v", "mom"):
            out[key] = param_specs
        elif key == "f":
            out[key] = jax.tree_util.tree_map(
                lambda spec, d: factored(spec, d),
                param_specs,
                sub,
                is_leaf=lambda x: isinstance(x, dict) and ("r" in x or "v" in x),
            )
        else:
            out[key] = _replicated_tree(sub, mesh)
    return out


def _choose_optimizer(arch_mod):
    name = getattr(arch_mod, "OPTIMIZER", "adamw")
    if name == "adafactor":
        return opt_lib.adafactor(1e-2)
    moment_dtype = getattr(arch_mod.CONFIG, "opt_state_dtype", "float32")
    return opt_lib.adamw(3e-4, moment_dtype=moment_dtype)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_cell(arch, arch_mod, cfg: TransformerConfig, shape: shp.LMShape, mesh) -> Cell:
    rules = dict(cfg.sharding_rules)
    key = jax.random.PRNGKey(0)

    if shape.kind == "train":
        optimizer = _choose_optimizer(arch_mod)
        step = steps.build_lm_train_step(cfg, optimizer)
        params_s = jax.eval_shape(functools.partial(transformer.init_params, cfg=cfg), key)
        opt_s = jax.eval_shape(optimizer.init, params_s)
        state_s = {"params": params_s, "opt": opt_s, "step": S((), jnp.int32)}
        batch_s = {
            "tokens": S((shape.global_batch, shape.seq_len), jnp.int32),
            "labels": S((shape.global_batch, shape.seq_len), jnp.int32),
        }
        param_specs = specs_for_tree(transformer.logical_axes(cfg), rules, mesh)
        state_sh = {
            "params": param_specs,
            "opt": _opt_shardings(opt_s, param_specs, mesh),
            "step": NamedSharding(mesh, PartitionSpec()),
        }
        batch_sh = {
            "tokens": _ns(mesh, rules, ("batch", None)),
            "labels": _ns(mesh, rules, ("batch", None)),
        }
        return Cell(arch, shape.name, "train", step, (state_s, batch_s),
                    (state_sh, batch_sh), rules, cfg, donate=(0,))

    scfg = dataclasses.replace(cfg, param_dtype="bfloat16", remat_policy="none",
                               microbatches=1)
    params_s = jax.eval_shape(functools.partial(transformer.init_params, cfg=scfg), key)
    param_specs = specs_for_tree(transformer.logical_axes(scfg), rules, mesh)

    if shape.kind == "prefill":
        fn = steps.build_lm_prefill_step(scfg, max_len=shape.seq_len)
        tokens_s = S((shape.global_batch, shape.seq_len), jnp.int32)
        return Cell(arch, shape.name, "prefill", fn, (params_s, tokens_s),
                    (param_specs, _ns(mesh, rules, ("batch", None))), rules, scfg)

    # decode: one new token against a full cache.  The cache sequence dim
    # carries the model axis (the batch dim cannot absorb 256-512 chips),
    # and the cache buffer is donated (in-place update, counted once).
    if shape.name == "long_500k":
        rules = {**rules, "cache_batch": None,
                 "cache_seq": ("pod", "data", "model")}
    else:
        rules = {**rules, "cache_seq": "model"}
    fn = steps.build_lm_decode_step(scfg)
    cache_s = jax.eval_shape(
        functools.partial(
            transformer.init_cache, scfg, shape.global_batch, shape.seq_len
        )
    )
    cache_sh = transformer.KVCache(
        k=_ns(mesh, rules, (None, "cache_batch", "cache_seq", "kv_heads", None)),
        v=_ns(mesh, rules, (None, "cache_batch", "cache_seq", "kv_heads", None)),
        length=NamedSharding(mesh, PartitionSpec()),
    )
    token_s = S((shape.global_batch, 1), jnp.int32)
    token_sh = _ns(mesh, rules, ("cache_batch", None))
    return Cell(arch, shape.name, "decode", fn,
                (params_s, cache_s, token_s),
                (param_specs, cache_sh, token_sh), rules, scfg, donate=(1,))


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def _gnn_graph_struct(cfg: GNNConfig, shape: shp.GNNShape):
    N, E = shape.n_nodes, shape.n_edges
    needs_pos = cfg.kind in ("schnet", "dimenet", "meshgraphnet", "graphcast")
    tri = None
    tri_mask = None
    if cfg.kind == "dimenet":
        T = shp.triplet_count(shape, cfg.triplet_factor)
        tri = S((T, 2), jnp.int32)
        tri_mask = S((T,), jnp.bool_)
    return gnn.GraphBatch(
        nodes=S((N, shape.d_feat), jnp.float32),
        edge_src=S((E,), jnp.int32),
        edge_dst=S((E,), jnp.int32),
        node_mask=S((N,), jnp.bool_),
        edge_mask=S((E,), jnp.bool_),
        positions=S((N, 3), jnp.float32) if needs_pos else None,
        edge_feat=None,
        graph_ids=S((N,), jnp.int32) if shape.n_graphs > 1 else None,
        triplets=tri,
        triplet_mask=tri_mask,
        n_graphs=shape.n_graphs,
    )


def _gnn_graph_shardings(cfg, shape, mesh, rules):
    n_ax = ("nodes",)
    e_ax = ("edges",)
    return gnn.GraphBatch(
        nodes=_ns(mesh, rules, n_ax + (None,)),
        edge_src=_ns(mesh, rules, e_ax),
        edge_dst=_ns(mesh, rules, e_ax),
        node_mask=_ns(mesh, rules, n_ax),
        edge_mask=_ns(mesh, rules, e_ax),
        positions=_ns(mesh, rules, n_ax + (None,))
        if cfg.kind in ("schnet", "dimenet", "meshgraphnet", "graphcast")
        else None,
        edge_feat=None,
        graph_ids=_ns(mesh, rules, n_ax) if shape.n_graphs > 1 else None,
        triplets=_ns(mesh, rules, e_ax + (None,)) if cfg.kind == "dimenet" else None,
        triplet_mask=_ns(mesh, rules, e_ax) if cfg.kind == "dimenet" else None,
        n_graphs=shape.n_graphs,
    )


def _gnn_cell(arch, arch_mod, cfg: GNNConfig, shape: shp.GNNShape, mesh) -> Cell:
    rules = dict(cfg.sharding_rules)
    optimizer = opt_lib.adamw(3e-4)
    step = steps.build_gnn_train_step(cfg, optimizer)
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(
        functools.partial(gnn.init_params, cfg=cfg, d_in=shape.d_feat, d_edge_in=4),
        key,
    )
    opt_s = jax.eval_shape(optimizer.init, params_s)
    state_s = {"params": params_s, "opt": opt_s, "step": S((), jnp.int32)}
    graph_s = _gnn_graph_struct(cfg, shape)
    graph_level = cfg.kind in ("schnet", "dimenet") and shape.n_graphs > 1
    target_s = (
        S((shape.n_graphs, cfg.d_out), jnp.float32)
        if graph_level
        else S((shape.n_nodes, cfg.d_out), jnp.float32)
    )
    batch_s = {"graph": graph_s, "target": target_s}

    param_specs = _replicated_tree(params_s, mesh)   # GNN weights are tiny
    state_sh = {
        "params": param_specs,
        "opt": _replicated_tree(opt_s, mesh),
        "step": NamedSharding(mesh, PartitionSpec()),
    }
    graph_sh = _gnn_graph_shardings(cfg, shape, mesh, rules)
    target_sh = (
        _ns(mesh, rules, ("batch", None))
        if graph_level
        else _ns(mesh, rules, ("nodes", None))
    )
    return Cell(arch, shape.name, "train", step,
                (state_s, {"graph": graph_s, "target": target_s}),
                (state_sh, {"graph": graph_sh, "target": target_sh}), rules, cfg,
                donate=(0,))


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _rec_cell(arch, arch_mod, cfg: RecsysConfig, shape: shp.RecShape, mesh) -> Cell:
    rules = dict(cfg.sharding_rules)
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(functools.partial(sasrec.init_params, cfg=cfg), key)
    param_specs = specs_for_tree(sasrec.logical_axes(cfg), rules, mesh)

    if shape.kind == "train":
        optimizer = opt_lib.adamw(1e-3)
        step = steps.build_sasrec_train_step(cfg, optimizer)
        opt_s = jax.eval_shape(optimizer.init, params_s)
        state_s = {"params": params_s, "opt": opt_s, "step": S((), jnp.int32)}
        batch_s = {
            k: S((shape.batch, cfg.seq_len), jnp.int32) for k in ("seqs", "pos", "neg")
        }
        state_sh = {
            "params": param_specs,
            "opt": _opt_shardings(opt_s, param_specs, mesh),
            "step": NamedSharding(mesh, PartitionSpec()),
        }
        batch_sh = {k: _ns(mesh, rules, ("batch", None)) for k in batch_s}
        return Cell(arch, shape.name, "train", step, (state_s, batch_s),
                    (state_sh, batch_sh), rules, cfg, donate=(0,))

    seqs_s = S((shape.batch, cfg.seq_len), jnp.int32)
    # batch=1 retrieval cannot shard the batch dim; parallelism lives on
    # the candidate/item axis instead.
    batch_ax = ("batch", None) if shape.batch > 1 else (None, None)
    seqs_sh = _ns(mesh, rules, batch_ax)
    if shape.kind == "score_all":
        # offline bulk scoring tiles the batch so logits stay bounded
        bc = 4096 if shape.batch > 8192 else None
        fn = lambda p, s: sasrec.score_all(p, s, cfg, top_k=10, batch_chunk=bc)
        return Cell(arch, shape.name, "score_all", fn, (params_s, seqs_s),
                    (param_specs, seqs_sh), rules, cfg)
    cand_s = S((shape.batch, shape.n_candidates), jnp.int32)
    cand_sh = _ns(mesh, rules, (None, "items"))
    fn = lambda p, s, c: sasrec.score_candidates(p, s, c, cfg)
    return Cell(arch, shape.name, "score_cand", fn, (params_s, seqs_s, cand_s),
                (param_specs, seqs_sh, cand_sh), rules, cfg)


# ---------------------------------------------------------------------------
# GraphGen (paper) cell
# ---------------------------------------------------------------------------

def _graphgen_banded_cell(arch, cfg, shape_name, mesh) -> Cell:
    """§Perf variant 'banded': shard_map PageRank with band-partitioned
    condensed edges (see repro.core.banding) — one all-gather + one
    psum-scatter per iteration instead of per-hop all-reduces (XLA cannot
    prove scatter locality from a flat edge list; shard_map states it)."""
    from jax.sharding import PartitionSpec as P

    from ..core.banding import make_banded_pagerank

    rules = dict(cfg.sharding_rules)
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n_sh = 1
    for a in axes:
        n_sh *= mesh.shape[a]
    vb_pad = cfg.n_virtual // n_sh + 2          # +2 inert pad slots per band
    fn = make_banded_pagerank(
        mesh, axes, cfg.n_real, n_sh * vb_pad, n_sh,
        iters=cfg.pagerank_iters,
    )
    eb = cfg.n_in_edges // n_sh
    cb = cfg.n_correction // n_sh
    args_s = {
        "in_src": S((cfg.n_in_edges,), jnp.int32),
        "in_dst": S((cfg.n_in_edges,), jnp.int32),
        "out_src": S((cfg.n_in_edges,), jnp.int32),
        "out_dst": S((cfg.n_in_edges,), jnp.int32),
        "corr_src": S((cfg.n_correction,), jnp.int32),
        "corr_dst": S((cfg.n_correction,), jnp.int32),
        "corr_cnt": S((cfg.n_correction,), jnp.float32),
        "deg": S((cfg.n_real,), jnp.float32),
    }
    sh = NamedSharding(mesh, P(axes))
    args_sh = {k: sh for k in args_s}
    return Cell(arch, shape_name, "analytics", fn, (args_s,),
                (args_sh,), rules, cfg)


# The dry-run's correction as one width class of this many slots a row
# (the row layout of repro.core.correction_rows; real graphs have several).
GRAPHGEN_CORRECTION_WIDTH = 8


def _graphgen_cell(arch, arch_mod, cfg, shape_name, mesh) -> Cell:
    from ..core import algorithms, engine
    from ..core.correction_rows import CorrectionRows, DeviceCorrection

    rules = dict(cfg.sharding_rules)

    def pagerank_step(args):
        in_src, in_dst = args["in_src"], args["in_dst"]
        fwd = engine.DeviceBipartite(in_src, in_dst, cfg.n_real, cfg.n_virtual)
        rev = engine.DeviceBipartite(in_dst, in_src, cfg.n_virtual, cfg.n_real)
        rows = CorrectionRows(
            (args["corr_idx"],), (args["corr_cnt"],), args["corr_row"]
        )
        g = engine.DeviceCondensed(
            chains=((fwd, rev),),
            direct=None,
            correction=DeviceCorrection(rows),
            diag_mult=None,
            n_real=cfg.n_real,
            deduplicated=False,
        )
        return algorithms.pagerank(g, num_iters=cfg.pagerank_iters)

    E, K = cfg.n_in_edges, GRAPHGEN_CORRECTION_WIDTH
    R = cfg.n_correction // K
    args_s = {
        "in_src": S((E,), jnp.int32),
        "in_dst": S((E,), jnp.int32),
        "corr_idx": S((R, K), jnp.int32),
        "corr_cnt": S((R, K), jnp.float32),
        "corr_row": S((cfg.n_real,), jnp.int32),
        "diag": S((cfg.n_real,), jnp.float32),
    }
    e_sh = _ns(mesh, rules, ("edges",))
    args_sh = {k: e_sh for k in args_s}
    args_sh["corr_row"] = _ns(mesh, rules, ("nodes",))
    args_sh["diag"] = _ns(mesh, rules, ("nodes",))
    return Cell(arch, shape_name, "analytics", pagerank_step, (args_s,),
                (args_sh,), rules, cfg)


# ---------------------------------------------------------------------------
# Serving replica placement (DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplicaPlacement:
    """One serving replica pinned to a contiguous device group."""

    tenant: str
    replica: int
    devices: Tuple[int, ...]


def place_serving_replicas(
    tenants,
    n_devices: int,
    *,
    group_size: int = 1,
    replicas: int = 1,
) -> list:
    """Place ``replicas`` serving replicas per tenant over ``n_devices``.

    Devices are carved into contiguous groups of ``group_size`` (a group
    is one :class:`~repro.serve.tier.GraphServingTier` process's mesh);
    tenant replicas go round-robin over the groups, so group load is
    balanced to within one replica and two replicas of the same tenant
    never share a group (they exist to survive that group).  Pure
    planning — no devices are touched; launchers consume the returned
    :class:`ReplicaPlacement` list.
    """
    tenants = list(tenants)
    if group_size <= 0 or n_devices < group_size:
        raise ValueError(
            f"need at least one group of {group_size} devices, have "
            f"{n_devices}"
        )
    groups = [
        tuple(range(g * group_size, (g + 1) * group_size))
        for g in range(n_devices // group_size)
    ]
    if replicas > len(groups):
        raise ValueError(
            f"{replicas} replicas per tenant need {replicas} distinct "
            f"device groups, have {len(groups)}"
        )
    # consecutive slots per tenant: replicas land on consecutive groups
    # (mod G), so with replicas <= len(groups) a tenant's replicas are
    # always disjoint, and sequential slot assignment keeps group load
    # balanced to within one replica
    out = []
    slot = 0
    for tenant in tenants:
        for r in range(replicas):
            out.append(ReplicaPlacement(
                tenant=tenant, replica=r,
                devices=groups[slot % len(groups)],
            ))
            slot += 1
    return out


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def build_cell(
    arch: str, shape: str, mesh: Mesh, smoke: bool = False,
    variant: Optional[str] = None,
) -> Cell:
    """``variant`` applies a documented beyond-baseline tweak:
    'a2a'      — MoE expert-parallel all-to-all dispatch (shard_map)
    'zero3'    — parameters sharded over the pod axis as well (DCI FSDP)
    'banded'   — graphgen band-partitioned shard_map propagation
    """
    mod = registry.get_arch(arch)
    cfg = mod.SMOKE if smoke else mod.CONFIG
    if variant == "a2a":
        if getattr(cfg, "moe", None) is None:
            raise ValueError(f"variant 'a2a' needs a MoE arch, got {arch}")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="a2a")
        )
    elif variant == "zero3":
        # params (and optimizer state) sharded over the pod axis too:
        # ZeRO-3 across DCI — the memory prescription for 405B-class train
        cfg = dataclasses.replace(
            cfg, sharding_rules={**cfg.sharding_rules,
                                 "embed_param": ("pod", "data")},
        )
    elif variant == "banded":
        if mod.SHAPE_FAMILY != "graphgen":
            raise ValueError("variant 'banded' applies to graphgen-paper")
        return _graphgen_banded_cell(arch, cfg, shape, mesh)
    elif variant is not None:
        raise ValueError(f"unknown variant {variant!r}")
    fam = mod.SHAPE_FAMILY
    if fam == "lm":
        return _lm_cell(arch, mod, cfg, shp.LM_SHAPES[shape], mesh)
    if fam == "gnn":
        return _gnn_cell(arch, mod, cfg, shp.GNN_SHAPES[shape], mesh)
    if fam == "recsys":
        return _rec_cell(arch, mod, cfg, shp.REC_SHAPES[shape], mesh)
    if fam == "graphgen":
        return _graphgen_cell(arch, mod, cfg, shape, mesh)
    raise ValueError(fam)


def all_cells() -> list:
    """The 40 assigned (arch x shape) pairs."""
    out = []
    for arch in registry.list_archs(assigned_only=True):
        for shape in registry.shapes_for(arch):
            out.append((arch, shape))
    return out
