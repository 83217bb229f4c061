"""Plain reference of a HACC I/O checkpoint run, and the check of a run.

The reference replays the seeded cycles in numpy, one correctly rounded
float32 add per value, on a sample of pages drawn from the seed (with the
first and last page of every range a cycle touched), starting from the
particles the benchmark made from the seed.  It imports nothing of the
program.  The check compares three things, each exactly:

* the storage file, read past the OS page cache, against the particles
  the device holds at the end (every byte);
* the storage file against the replay (every sampled particle, all nine
  fields);
* checkpoints begun in the window against checkpoints completed.
"""

from __future__ import annotations

import os

import numpy as np

from bench.gen.hacc import (DT, FLOATS, PAGE_PARTICLES, WARM_CYCLES, Plan,
                            layout)

#: pages of each field the replay follows, besides those at range edges
SAMPLE_PAGES = 512


def sample_pages(plan: Plan, cycles: int) -> np.ndarray:
    rng = np.random.default_rng([plan.seed, 3])
    picks = [rng.choice(plan.pages, min(SAMPLE_PAGES, plan.pages),
                        replace=False), [0, plan.pages - 1]]
    for i in range(cycles):
        c = plan.cycle(i)
        ends = c["starts"] + c["lens"]
        picks += [c["starts"], ends - 1, np.clip(c["starts"] - 1, 0, None),
                  np.clip(ends, None, plan.pages - 1)]
    return np.unique(np.concatenate([np.asarray(p, np.int64)
                                     for p in picks]))


def replay(plan: Plan, cycles: int, start: dict, pages: np.ndarray) -> dict:
    """The float fields on ``pages`` after ``cycles`` cycles, from their
    values ``start`` (each ``(len(pages), PAGE_PARTICLES)`` float32)."""
    s = {k: start[k].astype(np.float32).copy() for k in FLOATS}
    for i in range(cycles):
        c = plan.cycle(i)
        inside = ((pages[:, None] >= c["starts"][None])
                  & (pages[:, None] < (c["starts"] + c["lens"])[None])).any(1)
        for j, (x, v) in enumerate((("xx", "vx"), ("yy", "vy"),
                                    ("zz", "vz"))):
            nv = s[v][inside] + c["kick"][j]
            s[v][inside] = nv
            s[x][inside] = s[x][inside] + nv * DT
        s["phi"][inside] = s["phi"][inside] + c["dphi"]
    return s


def read_cold(path: str, offset: int, nbytes: int) -> np.ndarray:
    """``nbytes`` of ``path`` at ``offset``, read past the OS page cache."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        buf = np.empty(nbytes, np.uint8)
        view, done = memoryview(buf), 0
        while done < nbytes:
            got = os.preadv(fd, [view[done:]], offset + done)
            if got <= 0:
                break
            done += got
        return buf[:done]
    finally:
        os.close(fd)


def _by_page(a: np.ndarray, pages: np.ndarray, per_page: int) -> np.ndarray:
    return a.reshape(-1, per_page)[pages]


def check(config: dict, plan: Plan, cycles: int, final: dict, path: str,
          attempted: int) -> list[dict]:
    """The numbers compared, each with its limit (all exact: limit 0)."""
    from bench.gen.hacc import device_particles
    lay = layout(config)
    file_fields = {}
    device_diff = 0
    for k in lay["names"]:
        got = read_cold(path, lay["disp"][k], lay["bytes"][k])
        want = np.ascontiguousarray(final[k]).reshape(-1).view(np.uint8)
        if got.size != want.size:
            device_diff += abs(int(got.size) - int(want.size))
            got = np.resize(got, want.size)
        device_diff += int(np.count_nonzero(got != want))
        file_fields[k] = got.view(lay["dtypes"][k])
    # the replay starts from the particles made from the seed (set-up's
    # first checkpoint wrote them, the cycles changed them since)
    pages = sample_pages(plan, WARM_CYCLES + cycles)
    # only the sampled pages of the seed's particles cross to the host
    start = {k: np.asarray(v.reshape(plan.pages, -1)[pages])
             for k, v in device_particles(config, plan.seed).items()}
    start_pages = {k: start[k] for k in FLOATS}
    want = replay(plan, WARM_CYCLES + cycles, start_pages, pages)
    bad = np.zeros((len(pages), PAGE_PARTICLES), bool)
    for k in lay["names"]:
        per = lay["counts"][k] // plan.pages
        got = _by_page(file_fields[k], pages, per)
        w = want[k] if k in FLOATS else start[k]
        diff = got.view(np.uint8).reshape(len(pages), -1) != \
            np.ascontiguousarray(w).view(np.uint8).reshape(len(pages), -1)
        # bytes of one particle in this field, then any over them
        bad |= diff.reshape(len(pages), PAGE_PARTICLES, -1).any(-1)
    replay_bad = int(bad.sum())
    return [
        {"name": "file_vs_device_bytes", "value": device_diff, "limit": 0,
         "ok": device_diff == 0},
        {"name": "file_vs_replay_particles", "value": replay_bad,
         "limit": 0, "ok": replay_bad == 0},
        {"name": "checkpoints_failed", "value": attempted - cycles,
         "limit": 0, "ok": attempted == cycles},
    ]
