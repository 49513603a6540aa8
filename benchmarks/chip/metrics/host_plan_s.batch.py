"""Host planning seconds per census: pair-space construction and the
closed-form bases (``EngineStats.host_pair_seconds``, the
``census.plan`` spans) plus descriptor-window emission
(``host_emit_seconds``, the ``chunk.emit`` spans, summed over the
producer threads of a partitioned run), mean over the censuses (program
counters).  Nothing where the program does not time its planning."""


def read(record):
    if record["driver"] != "batch":
        return None
    sts = [c["stats"] for c in record["censuses"] if c["stats"]]
    plan = [st.get("host_pair_seconds", 0.0)
            + st.get("host_emit_seconds", 0.0) for st in sts]
    if not plan or not sum(plan):
        return None
    return sum(plan) / len(plan)
