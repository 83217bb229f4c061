"""Share of the traced window in which no operation ran on the device
(1 - busy union / window), in the cells that report ``train_tokens_per_s``."""


def read(run):
    return 100.0 * run.idle_share()
