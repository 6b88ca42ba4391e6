#!/usr/bin/env python3
"""``benchmark/rehearse.py`` with the watchdog's limit at a few seconds,
given through ``run_cell``'s argument: the run has to end itself and say in
which phase (tests/benchmark/test_bench_rehearsal.py)."""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'benchmark'))

import rehearse   # noqa: E402

LIMIT_S = 12


def main():
    from benchmark import session
    session.run_cell = functools.partial(session.run_cell,
                                         hard_limit_s=LIMIT_S)
    return rehearse.main()


if __name__ == '__main__':
    sys.exit(main())
