#!/usr/bin/env python
"""How often each compaction rung ran in a profiler trace, and for how long.

The push advance's gather-compacted windows run on one rung of a ladder of
static capacities (``repro.core.execute.compact_rungs``), under the scope
``compact.r<k>`` (``k = 0`` for the top rung).  This reads a trace that
``bench/profile.py --out DIR`` kept and prints one JSON line: for each
rung that ran inside the trace's ``bench.window`` span, ``runs``, the
number of times it ran (maximal stretches of device operations under its
scope, one per push level it served), and ``device_s``, its device time
(self time, as ``bench/scopes.py`` counts a scope).

    python bench/profile.py --workload kron-s20.bfs --seed 7 --seconds 25 \\
        --out /tmp/trace
    python tools/rung_profile.py /tmp/trace
"""
from __future__ import annotations

import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
RUNG = re.compile(r"compact\.r(\d+)$")


def rung_runs(ops: dict, lo: float, hi: float) -> dict:
    """``{scope: runs}`` over the operations that overlap ``[lo, hi]``.

    ``ops`` is ``{chip: [(op, start_ns, end_ns, scopes)]}`` as
    ``bench.scopes.read_events`` returns it; runs are counted per chip and
    averaged over the chips that ran any operation in the window.  A run
    ends at the first operation under a program scope and no rung: the
    copies and broadcasts XLA adds inside a rung's branch hold no program
    scope, and do not split it.
    """
    from repro.core.telemetry import SCOPES

    program = set(SCOPES)
    runs: dict[str, int] = {}
    chips = 0
    for chip_ops in ops.values():
        inside = sorted((a, held) for _, a, b, held in chip_ops
                        if min(b, hi) > max(a, lo))
        if not inside:
            continue
        chips += 1
        before: set = set()
        for _, held in inside:
            if held.isdisjoint(program):
                continue
            rungs = {s for s in held if RUNG.match(s)}
            for name in rungs - before:
                runs[name] = runs.get(name, 0) + 1
            before = rungs
    return {k: v / chips for k, v in runs.items()} if chips else {}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import scopes, trace

    path = args[0]
    if pathlib.Path(path).is_dir():
        path = trace.find_xplane(path)
    ops, spans = scopes.read_events(path)
    window = [s for s in spans if s[0] == trace.WINDOW_SPAN]
    if not window:
        raise SystemExit(f"no {trace.WINDOW_SPAN!r} span in {path}")
    _, lo, hi = window[0]
    runs = rung_runs(ops, lo, hi)
    names = sorted(runs, key=lambda s: int(RUNG.match(s).group(1)))
    seconds = scopes.reduce_events(ops, spans, names).scope_s
    print(json.dumps({name: {"runs": runs[name], "device_s": seconds[name]}
                      for name in names}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
