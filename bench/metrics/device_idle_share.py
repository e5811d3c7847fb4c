"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of operation intervals over the window)."""


def read(record):
    t = record["trace"]
    if not t["window_s"] or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
