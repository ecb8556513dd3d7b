"""Mixture-of-Experts with real expert parallelism (shard_map + all_to_all).

Used by deepseek-v3 (256 routed + 1 shared, top-8), granite-moe (32e top-8),
and jamba (16e top-2).

Design (DESIGN.md §4):
  * experts are sharded across the "model" mesh axis (EP); per-expert
    matrices are additionally FSDP-sharded on "data" and all-gathered
    manually inside the shard_map block (shard_map has no auto-resharding),
  * routing is top-k with a capacity factor; dropped tokens fall through the
    residual (standard GShard/Switch semantics),
  * dispatch/combine are jax.lax.all_to_all collectives along "model" —
    visible to the roofline parser as real collective traffic,
  * local expert compute is a dense grouped einsum over (E_local, capacity)
    buffers, so FLOP overcompute is bounded by the capacity factor (1.25x),
    not by E/k.

The whole block is differentiable (scatter/gather/all_to_all all have
transposes), so it trains under pjit with the surrounding auto-sharded code.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert_ff: int = 0    # deepseek: one always-on shared expert
    router_aux_weight: float = 0.01


def init_moe(key, cfg: MoEConfig, dtype) -> dict:
    ks = jax.random.split(key, 7)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    if cfg.shared_expert_ff:
        fs = cfg.shared_expert_ff
        p["shared_wi"] = (jax.random.normal(ks[4], (d, fs)) * s_in).astype(dtype)
        p["shared_wg"] = (jax.random.normal(ks[5], (d, fs)) * s_in).astype(dtype)
        p["shared_wo"] = (jax.random.normal(ks[6], (fs, d)) / np.sqrt(fs)).astype(dtype)
    return p


def _local_moe(params: dict, cfg: MoEConfig, x: jax.Array, *,
               ep_axis: Optional[str], fsdp_axis: Optional[str]):
    """Per-device MoE body.  x: (T_loc, D) local tokens.  Runs inside
    shard_map when ep_axis is set; single-device (no collectives) otherwise.
    Returns (y (T_loc, D), aux_loss scalar)."""
    t_loc, d = x.shape
    e = cfg.n_experts
    n_ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    e_loc = e // n_ep

    # ---- expert weights: manual FSDP all-gather along `fsdp_axis`
    wi, wg, wo = params["wi"], params["wg"], params["wo"]
    if fsdp_axis:
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axis, axis=2, tiled=True)

    # ---- routing (f32 result, bf16 contraction: keeps x's cotangent bf16 —
    # an f32 cast here promotes the whole activation-gradient path to f32,
    # doubling the backward all-gather traffic)
    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)   # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e fraction_e * prob_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=1),
        axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)

    # ---- capacity + positions (static: t_loc known at trace time)
    cap = max(1, int(np.ceil(cfg.capacity_factor * t_loc * cfg.top_k / e)))
    flat_expert = expert_idx.reshape(-1)                      # (T*K,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (T*K, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - 1   # (T*K, E)
    pos = jnp.max(pos_in_expert, axis=-1)                     # (T*K,)
    keep = (pos >= 0) & (pos < cap)
    safe_pos = jnp.where(keep, pos, cap - 1)

    # ---- dispatch: scatter tokens into (E, cap, D) buffers
    x_rep = jnp.repeat(x, cfg.top_k, axis=0)                  # (T*K, D)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_expert, safe_pos].add(
        jnp.where(keep[:, None], x_rep, 0))

    # ---- all_to_all to expert owners: (E, cap, D) -> (E_loc, n_ep*cap, D)
    # NOTE: we keep split_axis == concat_axis == 0 (shape-preserving) and do
    # the regrouping with explicit reshapes: the split!=concat form trips a
    # cotangent-layout bug in jax 0.8's all_to_all transpose under scan.
    if ep_axis:
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        # row block i now holds device i's tokens for MY local experts
        buf = buf.reshape(n_ep, e_loc, cap, d).swapaxes(0, 1)
        buf = buf.reshape(e_loc, n_ep * cap, d)
    else:
        buf = buf.reshape(e_loc, cap, d)
    wi_l, wg_l, wo_l = wi, wg, wo  # local expert slice under EP

    # ---- grouped dense expert compute
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg_l))
    hmid = g * jnp.einsum("ecd,edf->ecf", buf, wi_l)
    out = jnp.einsum("ecf,efd->ecd", hmid, wo_l)              # (E_loc, *, D)

    # ---- all_to_all back + combine (inverse regrouping, same axis form)
    if ep_axis:
        out = out.reshape(e_loc, n_ep, cap, d).swapaxes(0, 1)
        out = out.reshape(e, cap, d)
        out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0, tiled=True)
    y_tok = out[flat_expert, safe_pos]                        # (T*K, D)
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    y = jnp.sum((y_tok.reshape(t_loc, cfg.top_k, d)
                 * gate_vals[..., None].astype(y_tok.dtype)), axis=1)

    if cfg.shared_expert_ff:
        sg = jax.nn.silu(x @ params["shared_wg"])
        y = y + (sg * (x @ params["shared_wi"])) @ params["shared_wo"]
    return y, aux


def moe_block(params: dict, cfg: MoEConfig, x: jax.Array,
              mesh: Optional[Mesh]) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D).  With a mesh: shard_map over (dp..., model) with EP on
    "model".  Without: single-device reference path (tests)."""
    b, s, d = x.shape
    if mesh is None or "model" not in mesh.axis_names:
        y, aux = _local_moe(params, cfg, x.reshape(-1, d), ep_axis=None,
                            fsdp_axis=None)
        return y.reshape(b, s, d), aux

    dp_axes = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    fsdp = "data" if "data" in mesh.axis_names else None

    param_specs = {
        "router": P(None, None),
        "wi": P("model", fsdp, None),
        "wg": P("model", fsdp, None),
        "wo": P("model", None, fsdp),
    }
    if cfg.shared_expert_ff:
        param_specs.update({
            "shared_wi": P(fsdp, "model"),
            "shared_wg": P(fsdp, "model"),
            "shared_wo": P("model", fsdp),
        })
        # shared expert TP inside shard_map needs a psum; simpler: compute
        # the shared expert OUTSIDE shard_map under auto sharding.
        shared = {k: params[k] for k in
                  ("shared_wi", "shared_wg", "shared_wo")}
        routed = {k: v for k, v in params.items() if not k.startswith("shared")}
        cfg_no_shared = dataclasses.replace(cfg, shared_expert_ff=0)
        y, aux = moe_block(routed, cfg_no_shared, x, mesh)
        sg = jax.nn.silu(x @ shared["shared_wg"])
        return y + (sg * (x @ shared["shared_wi"])) @ shared["shared_wo"], aux

    fn = functools.partial(_local_moe, cfg=cfg, ep_axis="model",
                           fsdp_axis=fsdp)

    def body(p, xt):
        t = xt.reshape(-1, d)
        y, aux = fn(p, x=t)
        # replicate the aux scalar across the whole mesh so it can leave the
        # shard_map with an unsharded out_spec (check_vma=False below: the
        # static replication checker can't see through this psum pattern
        # when some axes carry replicated inputs, e.g. batch=1 decode)
        aux = jax.lax.pmean(aux, ("model",) + dp_axes)
        return y.reshape(xt.shape), aux

    # Tokens enter sharded over BOTH the dp axes (batch) and, when the seq
    # length allows, the "model" axis (seq) — so the per-device routing /
    # dispatch buffers shrink by the model-parallel degree (at deepseek
    # train_4k scale the (E, cap, D) buffer would otherwise be ~9 GB).
    # Axes that don't divide (batch=1 decode) are dropped: the tokens are
    # then replicated along them and every rank redundantly computes the
    # same (tiny) routed batch — correct, and irrelevant at decode sizes.
    n_model = mesh.shape["model"]
    seq_shardable = s % n_model == 0 and s >= n_model
    bdp = []
    prod = 1
    for a in dp_axes:
        if b % (prod * mesh.shape[a]) == 0:
            bdp.append(a)
            prod *= mesh.shape[a]
    x_spec = P(tuple(bdp) or None, "model" if seq_shardable else None, None)
    in_specs = ({k: param_specs[k] for k in params}, x_spec)
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(x_spec, P()), check_vma=False)(params, x)
    return y, aux
