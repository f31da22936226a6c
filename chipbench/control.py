"""The correctness check's control: a run of the benchmark with the control
in the program's place in the check. It must come out not correct where
the program's runs come out correct; the benchmark's own runs never read
it.

    python3 -m chipbench.control --kind stale --workload eager.wi50 \\
        --seed 5 --seconds 51 --trace 0

The control is the reference with one guarantee of the configuration
broken. ``stale`` answers each checked query without the last chunk of
transactions executed before its batch, which breaks "every answer covers
every transaction committed before its batch": the step a later change
that answers before propagating would take. The result line's ``check``
holds the control's numbers and ``correct`` follows from them; the
program's own numbers for the same answers go to standard error.
"""

from __future__ import annotations

import argparse
import sys

from chipbench import run

KINDS = ("stale",)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--kind", choices=KINDS, required=True)
    args, rest = p.parse_known_args(argv)
    return run.main(rest, control=args.kind)


if __name__ == "__main__":
    sys.exit(main())
