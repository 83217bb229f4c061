"""Seconds a resume spends restoring the checkpoint: the program's
``ckpt.restore`` spans in the window (read past the OS cache and CRC32 of
every array) over the resumes."""

from bench import spans


def read(run):
    return spans.per("ckpt.restore", "resumes", run)
