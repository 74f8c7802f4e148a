"""The comparison that decides `correct`.

The plain reference (benchmark/reference) verifies, on the host, the
sets of the groups a loop chose (a block's sets, in range sync) from the
exact wire bytes the node was given; a group verifies when all its sets
do.  Every answer the node gave for a chosen group is compared with that
verdict.  Answers that never came count with the wrong ones, for every
group.

A group that holds a planted bad set names its position as a hint: the
reference verifies that set first, and the rest only if it passes.  The
verdict is the same either way (one failing set fails the group); the
hint only spares the host the sets after it.

The verification runs in the worker processes of the run's pool, after
the window has closed and the node's state is freed.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from .reference import bls as RB

_PKS: List = []


def init_worker(pks) -> None:
    """Pool initializer: the reference's own pubkeys, one per keypair."""
    global _PKS
    _PKS = list(pks)


def verify_task(task) -> bool:
    key_ids, root, signature = task
    return RB.verify_set([_PKS[k] for k in key_ids], root, signature)


def reference_verdicts(
    executor, groups, chosen: Sequence[int], n_keys: int, hints: Dict[int, int] = None
) -> Dict[int, bool]:
    """The reference's verdict for each chosen group of wire sets.
    hints: group -> position of a set expected to fail."""
    hints = hints or {}

    def task(s):
        return (tuple(i % n_keys for i in s.indices), s.signing_root, s.signature)

    def verify(todo):
        tasks = [task(groups[g][p]) for g, p in todo]
        return [(g, bool(ok)) for (g, _p), ok in zip(todo, executor.map(verify_task, tasks, chunksize=4))]

    out = {g: True for g in chosen}
    first = [(g, hints[g]) for g in chosen if g in hints]
    first += [(g, p) for g in chosen if g not in hints for p in range(len(groups[g]))]
    for g, ok in verify(first):
        out[g] = out[g] and ok
    rest = [(g, p) for g in chosen if g in hints and out[g] for p in range(len(groups[g])) if p != hints[g]]
    for g, ok in verify(rest):
        out[g] = out[g] and ok
    return out


def compare(answers, reference: Dict[int, bool], n_due: int) -> Dict[str, int]:
    """answers: (group, verdict or None when none came).  Returns the
    compared count and the wrong and missing counts."""
    compared = wrong = missing = 0
    for g, verdict in answers:
        if verdict is None:
            missing += 1
        elif g in reference:
            compared += 1
            wrong += verdict != reference[g]
    missing += max(n_due - len(answers), 0)
    return {"compared": compared, "wrong": wrong, "missing": missing}
