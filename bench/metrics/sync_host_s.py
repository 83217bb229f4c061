"""The host's own seconds in a device sync, per checkpoint: the program's
``window.device_sync`` spans less the bitmap fetch (which waits for the
kernels) and the payload fetch within them, over the window's
checkpoints.  What is left is kernel launch, span rebuild, page-cache
apply, pwrite and fsync."""

from bench import spans


def read(run):
    sp = spans.program_spans()
    n = run.counters.get("checkpoints")
    if "window.device_sync" not in sp or not n:
        return None
    fetch = sum(sp[k]["s"] for k in ("window.fetch_bitmap",
                                     "window.fetch_payload") if k in sp)
    return (sp["window.device_sync"]["s"] - fetch) / n
