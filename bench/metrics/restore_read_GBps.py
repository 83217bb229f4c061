"""Storage read bandwidth of a restore: bytes over seconds of the
program's ``storage.read`` spans (the page cache's reads from the files)
that begin and end in the window."""

from bench import spans


def read(run):
    return spans.rate_GBps("storage.read")
