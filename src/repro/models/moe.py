"""Mixture-of-Experts with sort-based dispatch (EP over the model axis).

The dispatch avoids the classic (tokens, experts, capacity) one-hot tensor
-- intractable at 160 experts -- by computing each assignment's position
inside its expert with a cumsum over a (T, E) one-hot, scattering tokens
into an (E, capacity, D) buffer, running all experts as one batched einsum,
and gathering back.  With experts sharded over "model" and tokens over
"data", the scatter/gather is the all-to-all boundary GSPMD partitions
(see EXPERIMENTS.md §Perf for the explicit shard_map variant).

Router: softmax top-k with renormalized gates (DeepSeek-V2 style), plus the
standard load-balance auxiliary loss.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_act
from repro.runtime.sharding import current_mesh, shard

__all__ = ["moe_mlp", "moe_capacity", "moe_mlp_dense"]


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    cap = int(n_tokens * top_k / n_experts * capacity_factor) + 1
    return max(8, -(-cap // 8) * 8)  # pad to a multiple of 8


def moe_mlp(cfg, p, x: jax.Array, *, capacity: int | None = None):
    """Dispatcher: explicit-EP shard_map path under a mesh, dense otherwise.

    The dense (GSPMD) formulation computes assignment positions with a
    cumsum over the GLOBAL token axis, which forces the partitioner to
    all-gather every token and all-reduce f32 cotangents through the
    scatter (measured: 2 TiB all-gather + 5.4 TiB all-reduce per device
    per step on deepseek-v2 train_4k).  The shard_map path exploits that
    activations are already replicated over the "model" axis: dispatch is
    a LOCAL gather into the shard's own experts, and the combine is one
    bf16 psum -- see EXPERIMENTS.md §Perf.
    """
    mesh = current_mesh()
    if (mesh is not None and "model" in mesh.shape
            and cfg.n_experts % mesh.shape["model"] == 0):
        return _moe_mlp_shard_map(cfg, p, x, mesh, capacity=capacity)
    return moe_mlp_dense(cfg, p, x, capacity=capacity)


def _moe_mlp_shard_map(cfg, p, x, mesh, *, capacity=None):
    """Explicit expert parallelism.  x: (B, S, D) batch-sharded over the DP
    axes, replicated over "model"; expert weights sharded over "model"."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_dp = math.prod(mesh.shape[a] for a in dp) if dp else 1
    n_mp = mesh.shape["model"]
    T = B * S
    T_loc = max(1, T // n_dp)
    cap = capacity if capacity is not None else moe_capacity(
        T_loc, E, k, cfg.capacity_factor)
    E_loc = E // n_mp

    gated = "we_gate" in p  # static: selects the body signature

    def body(xf, router, *weights):
        we_up, we_down = weights[0], weights[-1]
        we_gate = weights[1] if gated else None
        # xf: (T_loc, D) local tokens; we_*: this shard's experts (E_loc,...)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        fe = jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32).mean(axis=0)
        aux = E * jnp.sum(fe * me)
        if dp:
            aux = jax.lax.pmean(aux, dp)

        e_flat = eidx.reshape(-1)
        g_flat = gates.reshape(-1)
        oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
        csum = jnp.cumsum(oh, axis=0) - oh            # LOCAL positions
        pos_in_e = jnp.take_along_axis(csum, e_flat[:, None], axis=1)[:, 0]
        keep = pos_in_e < cap
        tok = jnp.arange(T_loc * k) // k
        dest = jnp.where(keep, e_flat * cap + pos_in_e, E * cap)
        buf = jnp.zeros((E * cap + 1, D), xf.dtype).at[dest].set(xf[tok])
        buf = buf[:-1].reshape(E, cap, D)

        # my experts' slice of the (full-E, local-tokens) buffer
        j = jax.lax.axis_index("model")
        my = jax.lax.dynamic_slice_in_dim(buf, j * E_loc, E_loc, axis=0)
        h = jnp.einsum("ecd,edf->ecf", my, we_up)
        if gated:
            h = apply_act(h, jnp.einsum("ecd,edf->ecf", my, we_gate), cfg.act)
        else:
            h = apply_act(h, None, cfg.act)
        out_buf = jnp.einsum("ecf,efd->ecd", h, we_down)  # (E_loc, cap, D)

        # combine: my experts' contributions to local tokens, then psum
        out_flat = out_buf.reshape(E_loc * cap, D)
        local = jnp.where((e_flat >= j * E_loc) & (e_flat < (j + 1) * E_loc)
                          & keep, dest - j * E_loc * cap, E_loc * cap)
        padded = jnp.concatenate(
            [out_flat, jnp.zeros((1, D), out_flat.dtype)], axis=0)
        contrib = padded[jnp.minimum(local, E_loc * cap)]
        contrib = contrib * g_flat[:, None].astype(contrib.dtype)
        y = jnp.zeros((T_loc, D), xf.dtype).at[tok].add(contrib)
        return jax.lax.psum(y, "model"), aux

    xf = x.reshape(T, D)
    weights = ([p["we_up"], p["we_gate"], p["we_down"]] if gated
               else [p["we_up"], p["we_down"]])
    espec = P("model", None, None)
    in_specs = (P(dp, None), P(None, None)) + (espec,) * len(weights)
    out_specs = (P(dp, None), P())
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(xf, p["router"], *weights)
    y = y.reshape(B, S, D)

    # shared experts: dense TP path outside the shard_map
    if "ws_up" in p:
        hs = x.reshape(T, D) @ p["ws_up"]
        if "ws_gate" in p:
            hs = apply_act(hs, x.reshape(T, D) @ p["ws_gate"], cfg.act)
        else:
            hs = apply_act(hs, None, cfg.act)
        y = y + (hs @ p["ws_down"]).reshape(B, S, D)
    return y, aux


def moe_mlp_dense(cfg, p, x: jax.Array, *, capacity: int | None = None):
    """x: (B, S, D).  Returns (y, aux_loss).

    params: router (D,E); we_gate/we_up (E,D,F) [gated], we_down (E,F,D);
    optional shared-expert MLP ws_* fused over n_shared experts.
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, D)
    cap = capacity if capacity is not None else moe_capacity(
        T, E, k, cfg.capacity_factor)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                                # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)      # renorm

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = probs.mean(axis=0)                                              # (E,)
    onehot_top1 = jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32)
    fe = onehot_top1.mean(axis=0)
    aux = E * jnp.sum(fe * me)

    # -- position of every assignment inside its expert -----------------------
    e_flat = eidx.reshape(-1)                                            # (T*k,)
    g_flat = gates.reshape(-1)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)                      # (T*k,E)
    csum = jnp.cumsum(oh, axis=0) - oh  # exclusive count of same-expert predecessors
    pos_in_e = jnp.take_along_axis(csum, e_flat[:, None], axis=1)[:, 0]
    keep = pos_in_e < cap
    tok = jnp.arange(T * k) // k
    dest = jnp.where(keep, e_flat * cap + pos_in_e, E * cap)             # drop slot

    # -- dispatch: scatter tokens into (E, cap, D) ------------------------------
    buf = jnp.zeros((E * cap + 1, D), x.dtype).at[dest].set(xf[tok])
    buf = buf[:-1].reshape(E, cap, D)
    buf = shard(buf, ("experts", None, None), "moe.dispatch")

    # -- expert computation (batched over E) --------------------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["we_up"])
    if "we_gate" in p:
        g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])
        h = apply_act(h, g, cfg.act)
    else:
        h = apply_act(h, None, cfg.act)
    h = shard(h, ("experts", None, None), "moe.h")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    out_buf = shard(out_buf, ("experts", None, None), "moe.out")

    # -- combine: gather back + weighted scatter-add over tokens ------------------
    out_flat = out_buf.reshape(E * cap, D)
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.minimum(dest, E * cap - 1)], 0.0)
    contrib = contrib * g_flat[:, None].astype(contrib.dtype)
    y = jnp.zeros((T, D), x.dtype).at[tok].add(contrib)

    # -- shared experts (dense MLP over all tokens) --------------------------------
    if "ws_up" in p:
        hs = xf @ p["ws_up"]
        if "ws_gate" in p:
            hs = apply_act(hs, xf @ p["ws_gate"], cfg.act)
        else:
            hs = apply_act(hs, None, cfg.act)
        y = y + hs @ p["ws_down"]

    return y.reshape(B, S, D), aux
