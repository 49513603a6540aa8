"""Device busy seconds per census: the union of the intervals in which
an operation ran on a chip, averaged over the chips, over the censuses
of the traced window (device trace)."""


def read(record):
    done = record["attempted"] - record["failed"]
    if record["driver"] != "batch" or "trace" not in record or not done:
        return None
    busy = record["trace"]["busy_s"]
    return busy / done if busy > 0 else None
