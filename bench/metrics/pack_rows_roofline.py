"""Share of the HBM roofline the ``pack_rows`` kernel reaches in the window.

Bytes: each checkpoint reads every shard's flags and copies each dirty
page into the packed buffer (``bench.costs.pack_rows_bytes``; the dirty
pages are the program's ``payload_bytes`` counter over the page size).
Time: the device seconds of the kernel's operations in the trace.
"""

from bench import costs, tracing


def read(run):
    t = tracing.kernel_s(run.trace, "pack_rows")
    n = run.counters.get("checkpoints", 0)
    if t <= 0 or not n:
        return None
    need = (n * costs.pack_rows_bytes(run.counters["shard_bytes"], 0)
            + costs.pack_rows_bytes([], run.counters["dirty_pages"]))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
