"""Share of the HBM roofline the ``dirty_diff`` kernel reaches in the window.

Bytes: each checkpoint reads every shard's current and snapshot pages and
writes one flag per page (``bench.costs.dirty_diff_bytes``).  Time: the
device seconds of the kernel's operations in the trace.  The kernel only
moves bytes, so the HBM bandwidth bounds it.
"""

from bench import costs, tracing


def read(run):
    t = tracing.kernel_s(run.trace, "dirty_diff")
    n = run.counters.get("checkpoints", 0)
    if t <= 0 or not n:
        return None
    need = n * costs.dirty_diff_bytes(run.counters["shard_bytes"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
