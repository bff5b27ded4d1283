"""round.device_idle_pct: share of the traced round window in which no
operation ran on the device (1 - union of device-op intervals /
window)."""


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    return obs.trace.idle_pct()
