"""Share of the traced window in which no operation ran on the device,
averaged over the chips (device trace)."""


def read(record):
    if record["driver"] != "batch" or "trace" not in record:
        return None
    return record["trace"]["idle_pct"]
