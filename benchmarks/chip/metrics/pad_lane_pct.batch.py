"""Share of the lanes a census dispatches that are padding: 100 * the
dispatched lanes (``chunks * chunk_shape``) less the valid pre-prune
lanes, over the dispatched lanes, summed over the window's censuses
(program counters, ``EngineStats``); one chip only.  Nothing where the
program does not count its pre-prune lanes."""


def read(record):
    if record["driver"] != "batch" or record["chips"] != 1:
        return None
    sts = [c["stats"] for c in record["censuses"]
           if c["stats"].get("preprune_items")]
    lanes = sum(st["chunks"] * st["chunk_shape"] for st in sts)
    if not lanes:
        return None
    return 100.0 * (lanes - sum(st["preprune_items"] for st in sts)) / lanes
