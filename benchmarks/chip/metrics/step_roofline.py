"""Share of the chips' roofline that the census step reaches: the least
time the chips could take for the census's bytes (``work.py``: 8 bytes
per adjacency entry the census must visit, a function of the graph
alone) at the HBM peak of every chip used, over the device busy time
per census (device trace).  Bound by bytes: the census does no matrix
arithmetic, so no operation peak applies."""


def read(record):
    done = record["attempted"] - record["failed"]
    if record["driver"] != "batch" or "trace" not in record or not done:
        return None
    busy = record["trace"]["busy_s"] / done
    if busy <= 0:
        return None
    least = record["work_bytes"] / (record["chips"]
                                    * record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / busy
