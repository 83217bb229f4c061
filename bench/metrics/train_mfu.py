"""The training step's share of the chip's bf16 peak: model FLOPs of the
window's steps (``bench.costs.lm_train_flops``: 6 per matmul weight per
token plus causal attention, recomputation not counted) over the steps'
own times in the program's ``metrics_log`` (each ends in
``block_until_ready``), over the peak."""


def read(run):
    c = run.counters
    if not c.get("steps") or c.get("step_s", 0) <= 0:
        return None
    flops = c["step_flops"] * c["steps"]
    return 100.0 * flops / c["step_s"] / run.peaks["bf16_flops"]
