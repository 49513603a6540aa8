"""Host planning milliseconds per slide: pair-space maintenance, the
arc delta's CSR edit and descriptor emission (program counters,
``EngineStats.plan_host_seconds``), mean over the slides."""


def read(record):
    if record["driver"] != "stream":
        return None
    sts = [s["stats"] for s in record["slides"] if s["stats"]]
    if not sts:
        return None
    return 1e3 * sum(st["plan_host_seconds"] for st in sts) / len(sts)
