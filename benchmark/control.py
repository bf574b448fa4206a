#!/usr/bin/env python3
"""Run one cell with a fault planted (``faults.py``): the control on the
chip, at the cell's own size.

    python3 benchmark/control.py --plant early_stop --workload <cell>
        --seed <n> --seconds <s>

Prints the run's last line, as ``run.py`` does; its ``checks`` are the
control's readings. The benchmark's own runs never call this.
"""

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import faults  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plant", required=True, choices=sorted(faults.PLANTS))
    args, rest = ap.parse_known_args()
    return run.main(rest, plant=faults.PLANTS[args.plant])


if __name__ == "__main__":
    sys.exit(main())
