"""Share of the valid lanes a census dispatches that survive the prune
mask: 100 * the items classified over the pre-prune lanes, summed over
the window's censuses (program counters, ``EngineStats.items`` and
``EngineStats.preprune_items``); one chip only.  Nothing where the
program does not count its pre-prune lanes."""


def read(record):
    if record["driver"] != "batch" or record["chips"] != 1:
        return None
    sts = [c["stats"] for c in record["censuses"]
           if c["stats"].get("preprune_items")]
    if not sts:
        return None
    return (100.0 * sum(st["items"] for st in sts)
            / sum(st["preprune_items"] for st in sts))
