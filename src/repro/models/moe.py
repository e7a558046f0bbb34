"""Mixture-of-Experts layer: top-k routing with sort-based dispatch.

TPU-native dropping dispatch (MegaBlocks/GShard hybrid; see the MoE-LM
configs granite / moonshot):

1. router logits -> top-k gates per token (softmax over selected);
2. (token, expert) assignments flattened and sorted by expert id —
   the token<->expert incidence is a bipartite graph, and this is the
   same gather/segment machinery as the condensed-graph engine;
3. tokens scattered into an (E, C, D) capacity buffer (overflow dropped,
   capacity_factor-controlled), expert FFNs run as one batched einsum
   sharded over the expert axis (EP);
4. results weighted by gates and scattered back.

Aux losses: load-balancing (Switch) + router z-loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MoEConfig
from ..distributed.sharding import shard
from .layers import dense_init

__all__ = ["moe_init", "moe_apply", "moe_logical_axes"]


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, F = cfg.n_experts, cfg.d_expert
    return {
        "router": dense_init(kr, d_model, E, dtype),
        "w_gate": (
            jax.random.normal(kg, (E, d_model, F)) / jnp.sqrt(d_model)
        ).astype(dtype),
        "w_up": (
            jax.random.normal(ku, (E, d_model, F)) / jnp.sqrt(d_model)
        ).astype(dtype),
        "w_down": (
            jax.random.normal(kd, (E, F, d_model)) / jnp.sqrt(F)
        ).astype(dtype),
    }


def moe_logical_axes() -> Dict:
    return {
        "router": ("embed_param", "experts"),
        "w_gate": ("experts", "embed_param", "expert_ff"),
        "w_up": ("experts", "embed_param", "expert_ff"),
        "w_down": ("experts", "expert_ff", "embed_param"),
    }


def _route(params, x, cfg: MoEConfig):
    """Router top-k + aux losses (shared by both dispatch paths)."""
    E, K = cfg.n_experts, cfg.top_k
    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux_loss = cfg.aux_loss_weight * E * jnp.sum(density * mean_probs)
    z_loss = 1e-4 * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return eids, gates, aux_loss, z_loss


def _sort_positions(eids, gates, n_buckets: int, C: int, bucket_of):
    """Sort (token, k)-slots into per-bucket capacity positions.

    Returns (bucket, token, gate, pos, keep) arrays of length T*K, slot
    order sorted by bucket.  ``bucket_of`` maps expert id -> bucket id.
    """
    T, K = eids.shape
    flat_e = eids.reshape(-1)
    flat_b = bucket_of(flat_e)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_b)                            # stable
    sb, se, st, sg = flat_b[order], flat_e[order], flat_t[order], flat_g[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sb), sb, num_segments=n_buckets)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - start[sb].astype(jnp.int32)
    keep = pos < C
    return sb, se, st, sg, jnp.where(keep, pos, 0), keep


def _expert_ffn(params, buf, dtype, constrain=True):
    """(E, C, D) capacity buffer through the gated expert FFN."""
    h_g = jnp.einsum(
        "ecd,edf->ecf", buf, params["w_gate"].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    h_u = jnp.einsum(
        "ecd,edf->ecf", buf, params["w_up"].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    h = (jax.nn.silu(h_g) * h_u).astype(dtype)
    if constrain:  # no-op under shard_map (manual sharding)
        h = shard(h, "experts", "expert_capacity", "expert_ff")
    return jnp.einsum(
        "ecf,efd->ecd", h, params["w_down"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def _moe_sort(params, x, cfg: MoEConfig):
    """Baseline: global sort-based dispatch, XLA SPMD resolves layouts."""
    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    # Capacity-factor dropping at scale; dropless floor for small token
    # counts (decode / smoke) so serving matches full-context routing.
    C = max(int(T * K / E * cfg.capacity_factor), min(T, 128), 1)
    eids, gates, aux_loss, z_loss = _route(params, x, cfg)
    se, se_e, st, sg, pos_c, keep = _sort_positions(
        eids, gates, E, C, lambda e: e
    )
    buf = jnp.zeros((E, C, D), dtype=x.dtype)
    gathered = jnp.take(x, st, axis=0) * keep[:, None].astype(x.dtype)
    buf = buf.at[jnp.where(keep, se, 0), pos_c].add(gathered)
    buf = shard(buf, "experts", "expert_capacity", "embed")
    out_buf = _expert_ffn(params, buf, x.dtype)
    expert_out = out_buf[jnp.where(keep, se, 0), pos_c] * (
        sg * keep
    )[:, None].astype(x.dtype)
    y = jax.ops.segment_sum(expert_out, st, num_segments=T)
    y = shard(y, None, "embed")
    metrics = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_drop_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y, metrics


def _moe_a2a(params, x, cfg: MoEConfig, mesh, ep_axis: str, token_axes):
    """Expert-parallel all-to-all dispatch (shard_map; §Perf optimized).

    Tokens are partitioned across every mesh axis (``token_axes``); experts
    are partitioned over ``ep_axis`` and replicated elsewhere.  Each device
    routes its local tokens, buckets them *by destination EP rank*, and one
    ``all_to_all`` over ``ep_axis`` moves exactly T_local*K*D values there
    and back — instead of the baseline's all-reduce of the whole capacity
    buffer (measured 250x collective reduction on moonshot train_4k).
    """
    from jax.sharding import PartitionSpec as P

    E, K = cfg.n_experts, cfg.top_k
    n_ranks = 1
    for ax in ([ep_axis] if isinstance(ep_axis, str) else ep_axis):
        n_ranks *= mesh.shape[ax]
    E_loc = E // n_ranks

    def local_fn(x_loc, router, wg, wu, wd):
        T_loc, D = x_loc.shape
        rank = jax.lax.axis_index(ep_axis)
        p_loc = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        eids, gates, aux_loss, z_loss = _route(p_loc, x_loc, cfg)
        # capacity of each (destination rank) bucket
        C = max(int(T_loc * K / n_ranks * cfg.capacity_factor), 8)
        sb, se, st, sg, pos_c, keep = _sort_positions(
            eids, gates, n_ranks, C, lambda e: e // E_loc
        )
        sb_c = jnp.where(keep, sb, 0)
        send = jnp.zeros((n_ranks, C, D), x_loc.dtype)
        send = send.at[sb_c, pos_c].add(
            jnp.take(x_loc, st, axis=0) * keep[:, None].astype(x_loc.dtype)
        )
        send_e = jnp.full((n_ranks, C), -1, jnp.int32)
        send_e = send_e.at[sb_c, pos_c].max(
            jnp.where(keep, se, -1).astype(jnp.int32)
        )
        # the collective: tokens travel to their expert's EP rank and back
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
        recv_e = jax.lax.all_to_all(send_e, ep_axis, split_axis=0, concat_axis=0)

        # local dispatch into per-expert capacity slots (all local now)
        flat = recv.reshape(n_ranks * C, D)
        flat_e = recv_e.reshape(n_ranks * C)
        le = jnp.clip(flat_e - rank * E_loc, 0, E_loc - 1)
        valid = flat_e >= 0
        order = jnp.argsort(jnp.where(valid, le, E_loc))   # invalid last
        fe, fv = le[order], valid[order]
        C2 = max(int(n_ranks * C * cfg.capacity_factor / max(E_loc, 1)), 8)
        counts = jax.ops.segment_sum(
            fv.astype(jnp.int32), jnp.where(fv, fe, E_loc - 1), num_segments=E_loc
        )
        start = jnp.cumsum(counts) - counts
        pos2 = jnp.arange(n_ranks * C, dtype=jnp.int32) - start[fe].astype(jnp.int32)
        keep2 = (pos2 >= 0) & (pos2 < C2) & fv
        buf = jnp.zeros((E_loc, C2, D), x_loc.dtype)
        buf = buf.at[jnp.where(keep2, fe, 0), jnp.where(keep2, pos2, 0)].add(
            flat[order] * keep2[:, None].astype(x_loc.dtype)
        )
        out = _expert_ffn(p_loc, buf, x_loc.dtype, constrain=False)
        # undo the local dispatch
        flat_out = jnp.zeros((n_ranks * C, D), x_loc.dtype)
        flat_out = flat_out.at[order].set(
            out[jnp.where(keep2, fe, 0), jnp.where(keep2, pos2, 0)]
            * keep2[:, None].astype(x_loc.dtype)
        )
        back = jax.lax.all_to_all(
            flat_out.reshape(n_ranks, C, D), ep_axis, split_axis=0, concat_axis=0
        )
        contrib = back[sb_c, pos_c] * (sg * keep)[:, None].astype(x_loc.dtype)
        y = jnp.zeros_like(x_loc).at[st].add(contrib)
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
        # replicate scalars so out_specs=P() is legal
        all_axes = tuple(mesh.axis_names)
        aux_loss = jax.lax.pmean(aux_loss, all_axes)
        z_loss = jax.lax.pmean(z_loss, all_axes)
        drop = jax.lax.pmean(drop, all_axes)
        return y, aux_loss, z_loss, drop

    tok_spec = P(token_axes, None)
    w_spec3 = P(ep_axis, None, None)
    y, aux, zl, drop = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(tok_spec, P(None, None), w_spec3, w_spec3, w_spec3),
        out_specs=(tok_spec, P(), P(), P()),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    metrics = {
        "moe_aux_loss": jnp.mean(aux),
        "moe_z_loss": jnp.mean(zl),
        "moe_drop_fraction": jnp.mean(drop),
    }
    return y, metrics


def moe_apply(
    params: Dict, x: jnp.ndarray, cfg: MoEConfig
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (T, D) flattened tokens -> (T, D), aux metrics/losses."""
    if cfg.dispatch == "a2a":
        from ..distributed import sharding as shlib

        mesh, rules = shlib._ctx()
        ep_axis = rules.get("experts") if rules else None
        if (
            mesh is not None
            and isinstance(ep_axis, str)
            and ep_axis in mesh.axis_names
            and cfg.n_experts % mesh.shape[ep_axis] == 0
        ):
            token_axes = tuple(mesh.axis_names)  # tokens over every axis
            return _moe_a2a(params, x, cfg, mesh, ep_axis, token_axes)
        # no mesh / incompatible sharding: fall through to the baseline
    return _moe_sort(params, x, cfg)
