"""Mixture-of-Experts layer: top-k router + sort-based scatter dispatch.

Dispatch avoids the GShard one-hot combine tensor ([T, E, C] is hopeless at
kimi-k2 scale): tokens are flat-sorted by expert id, positioned within their
expert via rank arithmetic, and scattered into a dense [E, C, d] buffer whose
expert dim is sharded over the EP axis (XLA inserts the all-to-all). Overflow
beyond capacity C = ceil(T*k/E * capacity_factor) is dropped (tracked by the
aux loss, standard practice).
"""
from __future__ import annotations

import dataclasses

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .spec import PSpec
from . import layers


def moe_specs(cfg: ModelConfig, L=()) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    lax_ = tuple([None] * len(L))
    dt = cfg.dtype
    specs = {
        "router": PSpec(L + (d, e), lax_ + ("embed", None), jnp.float32),
        "w_gate": PSpec(L + (e, d, f), lax_ + ("experts", "embed", None), dt),
        "w_up": PSpec(L + (e, d, f), lax_ + ("experts", "embed", None), dt),
        "w_down": PSpec(L + (e, f, d), lax_ + ("experts", None, "embed"), dt),
    }
    if cfg.n_shared_experts:
        shared = dataclasses.replace(cfg, d_ff=cfg.d_ff * cfg.n_shared_experts)
        specs["shared"] = layers.mlp_specs(shared, L)
    return specs


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.n_experts) + 1
    return -(-c // 8) * 8  # keep the E-buffer lane-aligned


def apply_moe(cfg: ModelConfig, p: Dict, x: jax.Array, sh
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y, aux_loss).

    Distributed lowering uses the shard_map EP path (local dispatch + expert
    all-to-all). Under plain jit the token sort crosses the sharded token
    dim and XLA falls back to replicate-and-sort — measured at 33k
    all-gathers / 1.7 TB temps for kimi-k2 (EXPERIMENTS §Perf, rejected
    baseline)."""
    rules = getattr(sh, "rules", None)
    mesh = getattr(sh, "mesh", None)
    if (rules is not None and mesh is not None
            and x.shape[1] % mesh.shape[rules.model] == 0 and x.shape[1] > 1):
        return _apply_moe_spmd(cfg, p, x, sh, rules, mesh)
    return _apply_moe_local(cfg, p, x, sh)


def _apply_moe_local(cfg: ModelConfig, p: Dict, x: jax.Array, sh
                     ) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    e = cfg.n_experts
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, k)               # [t, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # ---- dispatch: sort (token, expert) pairs by expert --------------------
    flat_e = eidx.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate_vals.reshape(t * k)
    order = jnp.argsort(flat_e)                              # stable
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    cap = capacity(cfg, t)
    keep = pos_in_e < cap
    slot = jnp.where(keep, se.astype(jnp.int32) * cap + pos_in_e, e * cap)

    buf = jnp.zeros((e * cap, d), x.dtype).at[slot].set(xt[stok], mode="drop")
    buf = sh(buf.reshape(e, cap, d), "experts", None, None)

    # ---- expert FFN (swiglu), E sharded over the EP axis -------------------
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = sh(jax.nn.silu(g) * u, "experts", None, None)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = sh(out, "experts", None, None).reshape(e * cap, d)

    # ---- combine ------------------------------------------------------------
    contrib = out[jnp.minimum(slot, e * cap - 1)] * sgate[:, None].astype(x.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    y = jnp.zeros((t, d), x.dtype).at[stok].add(contrib)
    y = sh(y.reshape(b, s, d), "batch", "seq", "model_dim_act")

    if cfg.n_shared_experts:
        shared_cfg = dataclasses.replace(
            cfg, d_ff=cfg.d_ff * cfg.n_shared_experts)
        y = y + layers.apply_mlp(shared_cfg, p["shared"], x, sh)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                    # [e]
    ce = jnp.mean((jax.nn.one_hot(eidx, e, dtype=jnp.float32)
                   ).sum(1), axis=0)                                 # [e]
    aux = e * jnp.sum(me * ce)
    return y, aux


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_w_int8(w_local, axis_name: str, gather_axis: int):
    """FSDP weight gather with an int8 wire format (+ per-slice f32 scales).

    Halves the dominant collective term of giant-MoE training (the 3x-per-
    step expert weight gathers) at the cost of int8-quantized weights in the
    forward/recompute passes. Backward is exact: the gradient reduce-scatter
    (transpose of the gather) stays bf16.
    """
    return _gather_w_int8_impl(w_local, axis_name, gather_axis)


def _gather_w_int8_impl(w_local, axis_name, gather_axis):
    wf = w_local.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=gather_axis, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q, axis_name, axis=gather_axis, tiled=True)
    sg = jax.lax.all_gather(scale.astype(jnp.float32), axis_name,
                            axis=gather_axis, tiled=True)
    n = sg.shape[gather_axis]
    shape = qg.shape
    seg = shape[gather_axis] // n
    new_shape = shape[:gather_axis] + (n, seg) + shape[gather_axis + 1:]
    qr = qg.reshape(new_shape)
    sr = jnp.expand_dims(sg, gather_axis + 1)
    return (qr.astype(jnp.float32) * sr).reshape(shape).astype(w_local.dtype)


def _gather_w_int8_fwd(w_local, axis_name, gather_axis):
    return _gather_w_int8_impl(w_local, axis_name, gather_axis), None


def _gather_w_int8_bwd(axis_name, gather_axis, _, g):
    return (jax.lax.psum_scatter(g, axis_name,
                                 scatter_dimension=gather_axis, tiled=True),)


gather_w_int8.defvjp(_gather_w_int8_fwd, _gather_w_int8_bwd)


def _apply_moe_spmd(cfg: ModelConfig, p: Dict, x: jax.Array, sh, rules, mesh
                    ) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism the way real MoE frameworks run it: tokens stay
    local to their (data × sequence) shard, dispatch is a LOCAL sort, and
    only the dense [E, C, d] buffers cross the EP axis via all_to_all.

    Layout inside shard_map (full mesh):
      x            [b/|batch|, s/|model|, d]   per device
      w_gate/up/dn [E/|model|, ...]            per device (EP weights)
      buf          [E, C_loc, d] --all_to_all--> [E/|model|, |model|·C_loc, d]
    """
    import dataclasses as _dc

    ep = rules.model
    ep_size = mesh.shape[ep]
    batch_axes = tuple(a for a in rules.batch)
    all_axes = batch_axes + (ep,)
    e = cfg.n_experts
    P_ = jax.sharding.PartitionSpec

    f_ax = rules.fsdp
    use_int8 = (rules.moe_gather == "int8" and f_ax is not None
                and cfg.d_model % mesh.shape[f_ax] == 0)

    def shard_fn(xl, router, wg, wu, wd):
        if use_int8:  # manual int8-wire FSDP gather of expert weights
            wg = gather_w_int8(wg, f_ax, 1)
            wu = gather_w_int8(wu, f_ax, 1)
            wd = gather_w_int8(wd, f_ax, 2)
        b_l, s_l, d = xl.shape
        t = b_l * s_l
        xt = xl.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eidx = jax.lax.top_k(probs, cfg.experts_per_token)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        flat_e = eidx.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32),
                              cfg.experts_per_token)
        order = jnp.argsort(flat_e)                    # LOCAL sort
        se, stok = flat_e[order], flat_tok[order]
        sgate = gate_vals.reshape(-1)[order]
        starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
        pos = jnp.arange(se.shape[0], dtype=jnp.int32) \
            - starts[se].astype(jnp.int32)
        cap = capacity(cfg, t)
        keep = pos < cap
        slot = jnp.where(keep, se.astype(jnp.int32) * cap + pos, e * cap)

        buf = jnp.zeros((e * cap, d), xl.dtype).at[slot].set(
            xt[stok], mode="drop").reshape(e, cap, d)
        # EP exchange: experts -> owning rank, tokens from all ranks
        buf = jax.lax.all_to_all(buf, ep, split_axis=0, concat_axis=1,
                                 tiled=True)           # [E/ep, ep*cap, d]
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        out = jax.lax.all_to_all(out, ep, split_axis=1, concat_axis=0,
                                 tiled=True).reshape(e * cap, d)

        contrib = out[jnp.minimum(slot, e * cap - 1)] \
            * sgate[:, None].astype(xl.dtype)
        contrib = jnp.where(keep[:, None], contrib, 0)
        y = jnp.zeros((t, d), xl.dtype).at[stok].add(contrib)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(eidx, e, dtype=jnp.float32).sum(1), 0)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(b_l, s_l, d), aux

    if use_int8:  # weights enter shard_map still fsdp-sharded
        w_specs = (P_(ep, f_ax, None), P_(ep, f_ax, None), P_(ep, None, f_ax))
    else:         # XLA gathers the fsdp dim (bf16) at the shard_map boundary
        w_specs = (P_(ep, None, None),) * 3
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P_(batch_axes, ep, None), P_(None, None)) + w_specs,
        out_specs=(P_(batch_axes, ep, None), P_()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = sh(y, "batch", "seq", "model_dim_act")
    if cfg.n_shared_experts:
        shared_cfg = _dc.replace(cfg, d_ff=cfg.d_ff * cfg.n_shared_experts)
        y = y + layers.apply_mlp(shared_cfg, p["shared"], x, sh)
    return y, aux
