"""Work of the heaviest shard over the mean shard's, as the host's LPT
partitioning left it (program counter, ``EngineStats``); partitioned
runs only."""


def read(record):
    if record["driver"] != "batch":
        return None
    sts = [c["stats"] for c in record["censuses"]
           if c["stats"].get("partitioned")]
    if not sts:
        return None
    return sum(st["shard_max_over_mean"] for st in sts) / len(sts)
