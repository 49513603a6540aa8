"""Stream edges consumed per second, closed loop: every edge fed to the
monitor over the whole window (host clock)."""


def read(record):
    if record["driver"] != "stream" or not record["slides"]:
        return None
    return len(record["slides"]) * record["stride"] / record["window_s"]
