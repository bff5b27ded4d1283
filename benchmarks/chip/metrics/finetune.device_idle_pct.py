"""finetune.device_idle_pct: share of the traced fine-tune window in
which no operation ran on the device."""


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    return obs.trace.idle_pct()
