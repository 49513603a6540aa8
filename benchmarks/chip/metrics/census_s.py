"""Wall seconds per complete exact census: the whole window over the
censuses that completed in it (host clock)."""


def read(record):
    if record["driver"] != "batch":
        return None
    done = record["attempted"] - record["failed"]
    return record["window_s"] / done if done else None
