"""The control: the plain reference put in the program's place, with one
guarantee broken, to show that the comparison can fail.

The configuration guarantees exact verdicts.  The control's verdict for
a set is only whether its signature decodes to a curve point: no
subgroup check, no pairing.  A set signed by the wrong key or over the
wrong root then passes, and the run must come out `correct: false`.

    python3 -m benchmark.control --workload <cell> --seed <n> --seconds <s>

It runs the benchmark's own harness end to end (the same table, traffic,
service and comparison), on whatever device JAX has; the control itself
does no device work.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import sys

from . import run
from .reference import bls as RB


class DecodeOnlyVerifier:
    """The verifier surface the node's service drives (no begin_job:
    the service then verifies each merged job at resolve time)."""

    max_job_sets = 512

    def __init__(self, table, metrics):
        self.table = table
        self.metrics = metrics

    def can_accept_work(self) -> bool:
        return True

    def verify_signature_sets(self, sets, opts=None) -> bool:
        return all(RB.decodes(s.signature) for s in sets)

    def close(self) -> None:
        pass


def main(argv=None, hooks=None) -> int:
    return run.main(argv, {"any_device": True, "make_verifier": DecodeOnlyVerifier, **(hooks or {})})


if __name__ == "__main__":
    sys.exit(main())
