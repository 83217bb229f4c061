"""Storage flush bandwidth in the cells that report ``train_tokens_per_s``:
bytes written over seconds of the program's ``storage.flush`` spans (the
pwrite of the changed pages and the fsync) that begin and end in the
window."""

from bench import spans


def read(run):
    return spans.rate_GBps("storage.flush")
