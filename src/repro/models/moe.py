"""Mixture-of-Experts MLP (qwen2-moe, moonshot): shared + routed top-k.

Two execution paths:

* ``_moe_dense`` — single-device / test path: capacity-bounded
  scatter/gather dispatch (positions from a [T·k, E] cumsum), experts as
  one batched SwiGLU. FLOPs are 2·3·E·C·D·Fe — capacity_factor× the ideal
  top-k compute, not the E× blow-up of mask-dense MoE.

* sharded path (active mesh) — the dispatch is wrapped in shard_map:
  tokens stay LOCAL to their DP shard (per-shard capacity), expert FFN
  weights are tensor-parallel over 'model' on the Fe dim with a psum to
  combine partials (Megatron-style TP inside the expert). Without this,
  GSPMD replicates the global [E·C, D] dispatch buffer on every device
  (measured 43 GB/device at 256×4096 — EXPERIMENTS.md §Perf M5).

Aux load-balancing loss follows Switch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..utils import round_up
from .layers import dense_init

Array = jax.Array


def moe_init(key, cfg: ArchConfig) -> dict:
    E, D, Fe = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    std = 1.0 / (D ** 0.5)
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, Fe)) * std).astype(jnp.bfloat16),
        "w_up": (jax.random.normal(ks[2], (E, D, Fe)) * std).astype(jnp.bfloat16),
        "w_down": (
            jax.random.normal(ks[3], (E, Fe, D)) * (1.0 / Fe ** 0.5)
        ).astype(jnp.bfloat16),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * Fe
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kss[0], D, Fs),
            "w_up": dense_init(kss[1], D, Fs),
            "w_down": dense_init(kss[2], Fs, D),
        }
    return p


def _dispatch_compute(x2: Array, router: Array, wg: Array, wu: Array,
                      wd: Array, E: int, k: int, capacity_factor: float):
    """Core routed-expert compute on LOCAL tokens x2 [T, D].

    wg/wu/wd may be Fe-slices (TP inside shard_map); returns the PARTIAL
    output (caller psums over 'model' when sliced) and the aux loss.
    """
    T, D = x2.shape
    logits = jnp.dot(x2.astype(jnp.float32), router)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing auxiliary loss
    me = probs.mean(axis=0)  # [E]
    ce = jnp.zeros((E,)).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)

    C = round_up(max(int(T * k / E * capacity_factor), 8), 8)  # static
    fid = idx.reshape(-1)  # [T*k]
    onehot = jax.nn.one_hot(fid, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # position in expert
    valid = (pos < C)[:, None].astype(x2.dtype)
    slot = fid * C + jnp.minimum(pos, C - 1)

    xrep = jnp.repeat(x2, k, axis=0)  # token-major, matches idx.reshape(-1)
    buf = jnp.zeros((E * C, D), x2.dtype).at[slot].add(xrep * valid)
    buf = buf.reshape(E, C, D)

    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E * C, D)

    out = y[slot] * valid * gate_vals.reshape(-1, 1).astype(x2.dtype)
    return out.reshape(T, k, D).sum(axis=1), aux


def _moe_dense(p: dict, x: Array, cfg: ArchConfig, capacity_factor: float):
    B, S, D = x.shape
    out, aux = _dispatch_compute(
        x.reshape(B * S, D), p["router"], p["w_gate"], p["w_up"], p["w_down"],
        cfg.n_experts, cfg.moe_topk, capacity_factor,
    )
    return out.reshape(B, S, D), aux


def _moe_sharded(p: dict, x: Array, cfg: ArchConfig, capacity_factor: float,
                 mesh):
    """shard_map dispatch: DP-local tokens, Fe-TP experts (+psum 'model')."""
    from ..distributed.sharding import dp_axes, spec_with_fallback

    dp = dp_axes(mesh)
    B, S, D = x.shape
    Fe = p["w_gate"].shape[-1]
    tp = "model" in mesh.axis_names and Fe % mesh.shape["model"] == 0
    x_spec = spec_with_fallback(x.shape, [dp, None, None], mesh)
    w_spec = P(None, None, "model") if tp else P(None, None, None)
    wd_spec = P(None, "model", None) if tp else P(None, None, None)

    def local(x_l, router, wg, wu, wd):
        Bl, Sl, _ = x_l.shape
        out, aux = _dispatch_compute(
            x_l.reshape(Bl * Sl, D), router, wg, wu, wd,
            cfg.n_experts, cfg.moe_topk, capacity_factor,
        )
        if tp:
            out = jax.lax.psum(out, "model")
        if dp and x_spec[0] is not None:
            aux = jax.lax.pmean(aux, dp if len(dp) > 1 else dp[0])
        return out.reshape(Bl, Sl, D), aux

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, wd_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )
    return f(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_apply(p: dict, x: Array, cfg: ArchConfig, capacity_factor: float = 1.25):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    from ..distributed.sharding import _ACTIVE_MESH

    if _ACTIVE_MESH is not None and _ACTIVE_MESH.size > 1:
        out, aux = _moe_sharded(p, x, cfg, capacity_factor, _ACTIVE_MESH)
    else:
        out, aux = _moe_dense(p, x, cfg, capacity_factor)

    if cfg.n_shared_experts:  # shared experts: plain TP dense mlp
        B, S, D = x.shape
        x2 = x.reshape(B * S, D)
        sp = p["shared"]
        gs = jnp.dot(x2, sp["w_gate"])
        us = jnp.dot(x2, sp["w_up"])
        out = out + jnp.dot(
            jax.nn.silu(gs.astype(jnp.float32)).astype(x2.dtype) * us, sp["w_down"]
        ).reshape(B, S, D)
    return out, aux
