"""shards_live_pct (mesh), read as ``shards_live_pct.x4`` in the four-chip
cell: over the rounds of the window's sweeps, the share of (shard, round)
pairs in which the shard holds rows at or past the round's frontier (the
earliest ``n_hat`` of any lane still alive), in percent, from the round
record of ``execute_sweep``. A shard wholly before the frontier has no
live rows but still joins every psum: the rest of the mesh is idle work.
Silent where the run is not sharded (no ``shards`` in the driver's
observations)."""


def read(run):
    obs = run["obs"]
    record, shards = obs.get("round_record"), obs.get("shards")
    if record is None or not shards:
        return None
    num_rounds, boundaries = record["num_rounds"], record["boundaries"]
    local = obs["n_events"] // shards
    live = pairs = 0
    for j in range(int(num_rounds.max(initial=0))):
        frontier = int(boundaries[num_rounds > j, j].min())
        live += sum((k + 1) * local > frontier for k in range(shards))
        pairs += shards
    return 100.0 * live / pairs if pairs else None
