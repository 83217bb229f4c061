"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "ssd_scan_ref", "rg_lru_ref", "dirty_diff_ref",
           "diff_pack_ref"]

_NEG = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None,
                        t_actual=None):
    """q: (B,H,S,d); k/v: (B,K,T,d).  Naive full-matrix softmax attention."""
    B, H, S, d = q.shape
    _, K, T, _ = k.shape
    G = H // K
    scale = d ** -0.5 if scale is None else scale
    t_actual = T if t_actual is None else t_actual
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32) * scale,
                   kk.astype(jnp.float32))
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = k_pos < t_actual
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_scan_ref(x, dt, A, Bm, C):
    """Sequential SSD recurrence.  x: (B,H,S,P); dt: (B,H,S); A: (H,);
    Bm/C: (B,H,S,N) -> y (B,H,S,P) f32."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # (B,H,P),(B,H),(B,H,N),(B,H,N)
        da = jnp.exp(dt_t * Af[None, :])
        h = h * da[..., None, None] + jnp.einsum("bhn,bhp->bhnp", b_t,
                                                 x_t * dt_t[..., None])
        y = jnp.einsum("bhn,bhnp->bhp", c_t, h)
        return h, y

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(step, h0,
                         (xf.transpose(2, 0, 1, 3), dtf.transpose(2, 0, 1),
                          Bm.astype(jnp.float32).transpose(2, 0, 1, 3),
                          C.astype(jnp.float32).transpose(2, 0, 1, 3)))
    return ys.transpose(1, 2, 0, 3)  # (B,H,S,P)


def rg_lru_ref(a, gx):
    """Sequential gated recurrence.  a, gx: (B,S,W) -> y (B,S,W) f32."""
    af = a.astype(jnp.float32)
    gf = gx.astype(jnp.float32)

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (af.transpose(1, 0, 2), gf.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2)


def dirty_diff_ref(cur, snap):
    """(nblocks, block_elems) pair -> (nblocks,) int32 changed flags."""
    return jnp.any(cur != snap, axis=-1).astype(jnp.int32)


def diff_pack_ref(cur, snap):
    """(nblocks, ...) pair -> (flags, packed, count), packed[:count] = the
    changed blocks of ``cur`` in block order, rows past count zero."""
    flags = dirty_diff_ref(cur.reshape(cur.shape[0], -1),
                           snap.reshape(snap.shape[0], -1))
    order = jnp.argsort(1 - flags, stable=True)  # dirty blocks first
    count = jnp.sum(flags, keepdims=True)
    keep = jnp.arange(cur.shape[0]) < count
    packed = jnp.where(keep.reshape((-1,) + (1,) * (cur.ndim - 1)),
                       cur[order], 0).astype(cur.dtype)
    return flags, packed, count.astype(jnp.int32)
