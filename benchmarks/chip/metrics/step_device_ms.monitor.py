"""Device busy milliseconds per slide: the union of the intervals in
which an operation ran on a chip, averaged over the chips, over the
slides of the traced window (device trace)."""


def read(record):
    if (record["driver"] != "stream" or "trace" not in record
            or not record["slides"]):
        return None
    busy = record["trace"]["busy_s"]
    return 1e3 * busy / len(record["slides"]) if busy > 0 else None
