"""Plain reference of InternLM2 training: forward, loss, gradient, AdamW.

Straight ``jax.numpy`` in float32 at ``HIGHEST`` matmul precision, one
sequence at a time, no kernels, no remat, no sharding.  It follows the
published architecture (arXiv:2403.17297; the model's ``config.json``):
pre-norm decoder layers of RMSNorm, grouped-query attention with rotary
embeddings (the two halves of each head rotated, theta from the config),
and a SwiGLU MLP; a final RMSNorm and an untied head.  It imports nothing
of the program.  Two departures in form, not in value: the weights carry
the names and stacking of the checkpoint the program writes (one tensor a
kind, stacked over layers: ``g0/p0/wq`` is ``(layers, d, heads*128)``),
and an RMSNorm weight is held as ``scale`` with the weight ``1 + scale``.

``cast`` rounds every matmul operand before the product: the control
passes a float8 cast (``fp8_cast``), the precision below the bfloat16
that the configuration states for its matmuls.
"""

from __future__ import annotations

import math

import numpy as np

NORMS = ("final_norm", "g0/p0/norm1", "g0/p0/norm2")


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Weight name -> shape, in the checkpoint's naming."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, k, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    n = cfg["num_hidden_layers"]
    return {"embed/tok": (v, d), "final_norm": (d,), "lm_head": (d, v),
            "g0/p0/norm1": (n, d), "g0/p0/wq": (n, d, h * hd),
            "g0/p0/wk": (n, d, k * hd), "g0/p0/wv": (n, d, k * hd),
            "g0/p0/wo": (n, h * hd, d), "g0/p0/norm2": (n, d),
            "g0/p0/mlp_wi": (n, d, f), "g0/p0/mlp_wo": (n, f, d),
            "g0/p0/mlp_wg": (n, d, f)}


def make_weights(cfg: dict, seed: int):
    """The weights of a run, made on the device in one jitted call from the
    seed, float32: normal with std fan_in^-0.5 for matrices (the stacked
    layer axis is not fan-in), 0.02 for the embedding, scales 0."""
    import jax
    import jax.numpy as jnp
    shp = shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(sorted(shp)):
            s = shp[name]
            k = jax.random.fold_in(key, i)
            if name in NORMS:
                out[name] = jnp.zeros(s, jnp.float32)
            elif name == "embed/tok":
                out[name] = jax.random.normal(k, s, jnp.float32) * 0.02
            else:
                fan_in = s[-2]
                out[name] = (jax.random.normal(k, s, jnp.float32)
                             * fan_in ** -0.5)
        return out

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return make(jax.random.fold_in(key, seed >> 31))


def fp8_cast(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(a, b, cast):
    import jax
    import jax.numpy as jnp
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    import jax.numpy as jnp
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss_sum(w: dict, tokens, targets, cfg: dict, cast=None):
    """Sum of the cross-entropy of one sequence's valid targets (-1 =
    none), float32."""
    import jax
    import jax.numpy as jnp
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    x = w["embed/tok"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for li in range(cfg["num_hidden_layers"]):
        lw = {k[len("g0/p0/"):]: v[li] for k, v in w.items()
              if k.startswith("g0/p0/")}
        a = _rms(x, lw["norm1"], eps)
        q = _rope(_mm(a, lw["wq"], cast).reshape(s, h, hd), theta)
        k = _rope(_mm(a, lw["wk"], cast).reshape(s, kv, hd), theta)
        v = _mm(a, lw["wv"], cast).reshape(s, kv, hd)
        g = h // kv  # query head j reads key/value head j // g
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        qh, kh, vh = (jnp.transpose(t, (1, 0, 2)) for t in (q, k, v))
        sc = _mm(qh, jnp.transpose(kh, (0, 2, 1)), cast) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.transpose(_mm(p, vh, cast), (1, 0, 2)).reshape(s, h * hd)
        x = x + _mm(o, lw["wo"], cast)
        a = _rms(x, lw["norm2"], eps)
        gate = jax.nn.silu(_mm(a, lw["mlp_wg"], cast))
        x = x + _mm(gate * _mm(a, lw["mlp_wi"], cast), lw["mlp_wo"], cast)
    x = _rms(x, w["final_norm"], eps)
    logits = _mm(x, w["lm_head"], cast)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                             axis=-1)[:, 0]
    return jnp.sum(jnp.where(targets >= 0, lse - tl, 0.0))


class Reference:
    """Training steps of the reference: loss and gradient over a batch,
    row by row, then the stated AdamW."""

    def __init__(self, cfg: dict, opt: dict, cast=None):
        import jax
        self.cfg, self.opt = cfg, opt
        self._grad = jax.jit(jax.value_and_grad(
            lambda w, t, y: row_loss_sum(w, t, y, cfg, cast)))
        self._acc = jax.jit(lambda a, b: {k: a[k] + b[k] for k in a})
        self._adam = jax.jit(self._adam_step)

    def loss_and_grad(self, w: dict, tokens, targets):
        """Mean cross-entropy over the batch's valid targets, and its
        gradient.  tokens/targets: (batch, seq)."""
        import jax.numpy as jnp
        ntok = max(1, int((np.asarray(targets) >= 0).sum()))
        total, grads = 0.0, None
        for r in range(tokens.shape[0]):
            ls, g = self._grad(w, jnp.asarray(tokens[r]),
                               jnp.asarray(targets[r]))
            total += float(ls)
            grads = g if grads is None else self._acc(grads, g)
        return total / ntok, {k: v / ntok for k, v in grads.items()}

    def _adam_step(self, w, g, m, v, step):
        import jax.numpy as jnp
        o = self.opt
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        scale = jnp.minimum(1.0, o["clip_global_norm"] / jnp.maximum(gn, 1e-12))
        t = step.astype(jnp.float32)
        warm = jnp.minimum(1.0, (t + 1) / o["warmup_steps"])
        frac = jnp.clip((t - o["warmup_steps"])
                        / (o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
        lr = o["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        b1c = 1 - o["b1"] ** (t + 1)
        b2c = 1 - o["b2"] ** (t + 1)
        nw, nm, nv, gc = {}, {}, {}, {}
        for k in w:
            gk = g[k] * scale
            gc[k] = gk
            nm[k] = o["b1"] * m[k] + (1 - o["b1"]) * gk
            nv[k] = o["b2"] * v[k] + (1 - o["b2"]) * gk * gk
            upd = (nm[k] / b1c) / (jnp.sqrt(nv[k] / b2c) + o["eps"])
            if k not in NORMS:
                upd = upd + o["weight_decay"] * w[k]
            nw[k] = w[k] - lr * upd
        return nw, nm, nv, gc

    def run(self, w: dict, batches: list) -> dict:
        """Steps over ``batches``; per step its loss, the first step's
        clipped gradient (as the optimizer gets it) and the weights after
        the last step."""
        import jax.numpy as jnp
        m = {k: jnp.zeros_like(x) for k, x in w.items()}
        v = {k: jnp.zeros_like(x) for k, x in w.items()}
        losses, first = [], None
        for i, (tok, tgt) in enumerate(batches):
            loss, g = self.loss_and_grad(w, tok, tgt)
            losses.append(loss)
            w, m, v, gc = self._adam(w, g, m, v, jnp.int32(i))
            if first is None:
                first = gc
            del g
        return {"losses": losses, "grad0": first, "weights": w}
