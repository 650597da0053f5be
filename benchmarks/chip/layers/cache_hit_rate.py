"""Cache: share of the window's cache lookups served from the memory tier,
from the change of ``CacheManager.stats`` over the window (percent)."""


def read(obs: dict):
    d = obs.get("cache_delta")
    if not d or d["hits"] + d["misses"] == 0:
        return None
    return 100.0 * d["hits"] / (d["hits"] + d["misses"])
