"""Device, analytics cells: percent of the traced window in which no operation
ran on the chip (1 minus the union of device-op intervals over the window)."""


def read(obs: dict):
    t = obs.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
