#!/usr/bin/env python3
"""Record the small profiler trace that ``test_chipbench_trace.py``
reduces: a few chunks of one census, with the harness's host spans,
written as a gzipped ``.xplane.pb``.

    python3 benchmarks/chip/record_trace.py --out trace_small.xplane.pb.gz
"""

from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--max-items", type=int, default=1 << 18)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]
    import jax

    from chip import drive, gen
    from repro.core import CensusEngine, from_edges
    src, dst = gen.citation_arcs(args.n, round(args.n * 4.376), 3.126,
                                 seed=0)
    g = from_edges(src, dst, n=args.n)
    engine = CensusEngine(backend="jnp")
    kw = {"orient": "degree", "max_items": args.max_items}
    engine.run(g, **kw)                        # compile outside the trace
    spans = drive.Spans(True)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        with jax.profiler.trace(tmp):
            with spans("window"):
                with spans("census"):
                    engine.run(g, progress=lambda *_: spans.mark("chunk"),
                               **kw)
                    spans.close_mark()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        with open(found[0], "rb") as f, gzip.open(args.out, "wb") as out:
            out.write(f.read())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{args.out}: {engine.stats.chunks} chunks on "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
