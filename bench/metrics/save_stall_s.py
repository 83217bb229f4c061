"""Seconds a save holds training up: the window's time outside the steps'
own times (``metrics_log``), over the saves begun in the window.  It holds
the save's staging (device-to-host copy, CRC32, snapshot diff and copy)
and the wait for the previous save's flush."""


def read(run):
    c = run.counters
    if not c.get("saves"):
        return None
    return (c["window_s"] - c["step_s"]) / c["saves"]
