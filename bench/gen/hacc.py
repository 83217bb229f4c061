"""HACC I/O particles and the updates between checkpoints, from the seed.

One general generator for every HACC traffic mix.  A mix is a JSON file
in ``bench/traffic``: each cycle ``ranges`` contiguous particle ranges,
one in each of ``ranges`` equal segments of the rank's particles, get a
kick and a drift in their 7 float fields.  The ranges are whole pages
(1024 particles: one 4 KiB page of a float32 field) and together hold
``dirty_share`` of the rank's pages, the same count in every cycle and
for every seed.  How that count splits among the ranges, where each range
lies in its segment, and the kick and drift constants are drawn from
``--seed`` anew for each cycle.

``pid`` (int64, held as its two little-endian uint32 words: JAX runs
without x64) and ``mask`` (uint16) never change after the first
checkpoint.  Cycles ``0 .. WARM_CYCLES-1`` warm the programs up in
set-up; the window runs the cycles after them.
"""

from __future__ import annotations

import numpy as np

PAGE = 4096
#: particles in one page of a float32 field
PAGE_PARTICLES = PAGE // 4
#: the drift's time step: a power of two, so ``v * DT`` is exact
DT = np.float32(2.0 ** -6)
#: box side of the initial positions
BOX = 256.0
FLOATS = ("xx", "yy", "zz", "vx", "vy", "vz", "phi")
#: cycles run in set-up: every cycle has the same shapes, so one warms
#: every program the window runs
WARM_CYCLES = 1


def layout(config: dict) -> dict:
    """Field names, element dtypes, element counts, bytes and byte
    displacement of each field in the rank's segment."""
    n = int(config["particles"])
    if n % (2 * PAGE_PARTICLES):
        raise ValueError(f"particles {n} is not a multiple of "
                         f"{2 * PAGE_PARTICLES}: a field would end mid-page")
    names, dtypes, counts, nbytes, disp = [], {}, {}, {}, {}
    off = 0
    for name, dtype in config["fields"]:
        names.append(name)
        if dtype == "int64":  # two uint32 words per particle
            dtypes[name], counts[name] = np.dtype(np.uint32), 2 * n
        else:
            dtypes[name], counts[name] = np.dtype(dtype), n
        nbytes[name] = counts[name] * dtypes[name].itemsize
        disp[name] = off
        off += nbytes[name]
    if off != n * int(config["record_bytes"]):
        raise ValueError(f"fields hold {off} bytes, not "
                         f"{config['record_bytes']} per particle")
    return {"names": names, "dtypes": dtypes, "counts": counts,
            "bytes": nbytes, "disp": disp, "rank_bytes": off}


def _key(seed: int, stream: int):
    import jax
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), stream)


def device_particles(config: dict, seed: int) -> dict:
    """The rank's particles on the device, made in one jitted call."""
    import jax
    import jax.numpy as jnp
    n = int(config["particles"])

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 8)
        out = {}
        for i, name in enumerate(("xx", "yy", "zz")):
            out[name] = jax.random.uniform(ks[i], (n,), jnp.float32, 0, BOX)
        for i, name in enumerate(("vx", "vy", "vz", "phi")):
            out[name] = jax.random.normal(ks[3 + i], (n,), jnp.float32)
        # little-endian int64 ids: the low word, then a zero high word
        # (no (n, 2)-shaped array: its minor dim would pad to 128 lanes)
        w = jnp.arange(2 * n, dtype=jnp.uint32)
        out["pid"] = jnp.where(w % 2 == 0, w // 2, 0).astype(jnp.uint32)
        out["mask"] = jax.random.randint(ks[7], (n,), 0, 1 << 16,
                                         jnp.int32).astype(jnp.uint16)
        return out

    return make(_key(seed, 0))


def device_mask(pages: int, starts, lens):
    """Per-particle bool mask of a cycle's page ranges.  Traced by
    ``jax.jit``; its own program, so the update reads it as an array
    (fused into the update it would be recomputed for every value)."""
    import jax.numpy as jnp
    d = jnp.zeros(pages + 1, jnp.int32).at[starts].add(1)
    d = d.at[starts + lens].add(-1)
    return jnp.repeat(jnp.cumsum(d)[:pages] > 0, PAGE_PARTICLES)


def device_update(state: dict, mask, kick, dphi) -> dict:
    """One cycle's kick and drift on the device where ``mask`` holds.
    Traced by ``jax.jit``."""
    import jax.numpy as jnp

    def sel(new, old):
        return jnp.where(mask, new, old)

    out = dict(state)
    for i, (x, v) in enumerate((("xx", "vx"), ("yy", "vy"), ("zz", "vz"))):
        nv = state[v] + kick[i]
        out[v] = sel(nv, state[v])
        out[x] = sel(state[x] + nv * DT, state[x])
    out["phi"] = sel(state["phi"] + dphi, state["phi"])
    return out


class Plan:
    """The cycles of one run: page ranges and constants of cycle ``i``,
    the same for the same seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = seed
        self.pages = int(config["particles"]) // PAGE_PARTICLES
        self.ranges = int(traffic["ranges"])
        self.seg = self.pages // self.ranges
        self.dirty_pages = int(round(traffic["dirty_share"] * self.pages))
        if not self.ranges <= self.dirty_pages <= self.ranges * self.seg:
            raise ValueError(f"{self.dirty_pages} dirty pages cannot fill "
                             f"{self.ranges} ranges of 1..{self.seg} pages")

    def lengths(self, rng) -> np.ndarray:
        """``dirty_pages`` split into ``ranges`` lengths of 1..seg pages,
        in shares drawn from ``rng``."""
        n, r = self.dirty_pages, self.ranges
        while True:
            ln = 1 + np.floor(rng.dirichlet(np.ones(r)) * (n - r)).astype(
                np.int64)
            ln[rng.choice(r, n - int(ln.sum()), replace=False)] += 1
            if ln.max() <= self.seg:
                return ln

    def cycle(self, i: int) -> dict:
        """Cycle ``i``'s arguments of :func:`device_mask` and
        :func:`device_update` (numpy)."""
        rng = np.random.default_rng([self.seed, 2, i])
        lens = self.lengths(rng)
        offs = rng.integers(0, self.seg - lens + 1)
        starts = np.arange(self.ranges) * self.seg + offs
        kick = (rng.standard_normal(3) * 0.01).astype(np.float32)
        dphi = np.float32(rng.standard_normal() * 0.01)
        return {"starts": starts.astype(np.int32),
                "lens": lens.astype(np.int32), "kick": kick, "dphi": dphi}
