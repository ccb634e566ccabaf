"""Print the outline of a profiler trace: each plane's lines with their
event counts and the operation names that take the most time.

    python3 bench/tools/trace_outline.py bench/_out/trace

Look at a trace this way before matching operation names in a metric's
reader (``bench/metrics``).
"""
from __future__ import annotations

import argparse
import glob
import sys
from pathlib import Path


def outline(xplane: Path, top: int = 12) -> str:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            tot = {}
            n = 0
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
            names = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  line {line.name!r}: {n} events; "
                       + "; ".join(f"{k} {v / 1e6:.3f} ms" for k, v in names))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", type=Path,
                    help="a .xplane.pb file, or a directory holding one")
    args = ap.parse_args(argv)
    for f in glob.glob(str(args.trace / "**" / "*.xplane.pb"),
                       recursive=True) or [str(args.trace)]:
        print(f"# {f}\n{outline(Path(f))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
