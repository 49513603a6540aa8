"""Host landing seconds per census: the host blocked on a chip's result,
then its int64 merge (``EngineStats.host_land_seconds``, the
``chunk.land`` spans), mean over the censuses (program counter)."""


def read(record):
    if record["driver"] != "batch":
        return None
    sts = [c["stats"] for c in record["censuses"]
           if c["stats"].get("host_land_seconds")]
    if not sts:
        return None
    return sum(st["host_land_seconds"] for st in sts) / len(sts)
