"""cache_hit_pct (service), read as ``cache_hit_pct.serve`` in the service
cell: the service's answer-cache hits over hits and misses in the window,
in percent, from ``svc.stats``."""


def read(run):
    obs = run["obs"]
    asked = obs.get("hits", 0) + obs.get("misses", 0)
    if not asked:
        return None
    return 100.0 * obs["hits"] / asked
