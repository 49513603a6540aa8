"""Host partitioning seconds per census: LPT pair sharding, shard
extraction and the shard schedule (``EngineStats
.host_partition_seconds``, the ``census.partition`` spans), mean over
the censuses (program counter); partitioned runs only."""


def read(record):
    if record["driver"] != "batch":
        return None
    sts = [c["stats"] for c in record["censuses"]
           if c["stats"].get("partitioned")
           and c["stats"].get("host_partition_seconds")]
    if not sts:
        return None
    return sum(st["host_partition_seconds"] for st in sts) / len(sts)
