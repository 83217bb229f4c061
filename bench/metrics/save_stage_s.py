"""Seconds a save's staging holds training up: the program's ``ckpt.stage``
spans in the window (each array's device-to-host fetch, CRC32, snapshot
copy and page diff) over the saves begun in it."""

from bench import spans


def read(run):
    return spans.per("ckpt.stage", "saves", run)
