"""Megabytes shipped to a chip per census: the resident graph of the
fullest chip, plus the plan bytes (descriptor windows, padding
included) shipped to a chip on average (program counters,
``EngineStats``)."""


def _bytes(st):
    ndev = max(st.get("ndev", 1), 1)
    if st.get("partitioned"):
        plan = (st["plan_upload_bytes_total"]
                + st["plan_pad_bytes_total"]) / ndev
    else:
        plan = st["plan_upload_bytes"] * st["chunks"]
    return st["graph_resident_bytes"] + plan


def read(record):
    if record["driver"] != "batch":
        return None
    sts = [c["stats"] for c in record["censuses"] if c["stats"]]
    if not sts:
        return None
    return sum(_bytes(st) for st in sts) / len(sts) / 1e6
