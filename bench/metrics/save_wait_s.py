"""Seconds a save waits for the previous save's flush: the program's
``ckpt.wait`` spans in the window over the saves begun in it."""

from bench import spans


def read(run):
    return spans.per("ckpt.wait", "saves", run)
