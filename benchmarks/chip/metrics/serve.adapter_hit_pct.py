"""serve.adapter_hit_pct: the modulator store's LRU hits / (hits +
misses) over the window, from the store's own counters."""


def read(obs):
    c = obs.counters
    total = c.get("hits", 0) + c.get("misses", 0)
    if not total:
        return None
    return 100.0 * c["hits"] / total
