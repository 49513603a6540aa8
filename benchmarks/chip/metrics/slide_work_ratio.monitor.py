"""Work items a slide recounted over the items a full recompute of the
same window would count (program counters, ``EngineStats.items /
full_items``), mean over the slides."""


def read(record):
    if record["driver"] != "stream":
        return None
    ratios = [s["stats"]["items"] / s["stats"]["full_items"]
              for s in record["slides"]
              if s["stats"] and s["stats"].get("full_items")]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
