"""The controls: the reference put in the program's place with ONE
guarantee of the configuration broken, which the comparison of
``verify.py`` has to find not correct.  The system runs no model and
states no precision, so a control is the step that would tempt a later
PR to buy speed with:

- ``stale_version``: a write is acknowledged before it is applied, so
  what is read back (and what the stores hold) is the version before
  the last acknowledged one, and a read in the window sees the version
  before the one it saw;
- ``no_decode``: a read of an object with a lost data shard is
  answered from the surviving data shards without reconstruction: the
  lost chunks read as zeros.

Not part of the benchmark's command.  On the chip, at the cell's own
size:

    python3 -m benchmark.control --workload <cell> --seed <n> --seconds <s>

runs the cell as the command does and then compares twice: the program
(has to be correct) and the control in its place (has to be not
correct).  ``benchmark/tests/`` keeps the same at a small size.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

from . import reference, verify
from .generators.closed_loop import TAG


def _key(name: str) -> int:
    return int(name[3:])


class StaleVersion:
    def __init__(self, plan, ops, populated, k, m, unit, placement):
        self.plan, self.k, self.m, self.unit = plan, k, m, unit
        self.hists = verify.histories(ops, populated)

    def _stale(self, key: int) -> bytes:
        newest = self.hists[key].candidates(verify.INF, verify.INF)[0]
        older = [v for v in self.hists[key].writes if v < newest]
        if not older:
            return bytes(self.plan.object_bytes)
        return bytes(self.plan.payload(key, max(older)))

    def read(self, name: str) -> bytes:
        return self._stale(_key(name))

    def stored_shards(self, name: str) -> dict[int, bytes]:
        return dict(enumerate(reference.encode(
            self._stale(_key(name)), self.k, self.m, self.unit)))

    def answer(self, op) -> bytes:
        """One version older than what the read saw."""
        if self.plan.tag:
            _k, v = TAG.unpack_from(op.answer)
            return self.plan.payload(op.key, v - 1) if v else op.answer
        return op.answer


class NoDecode:
    def __init__(self, plan, ops, populated, k, m, unit, placement):
        self.plan, self.k, self.m, self.unit = plan, k, m, unit
        self.placement = placement

    def _holey(self, key: int) -> bytes:
        payload = bytes(self.plan.payload(key, 0))
        streams = reference.scatter(payload, self.k, self.unit)
        for pos, osd in enumerate(
                self.placement(self.plan.name(key))[:self.k]):
            if osd is None:
                streams[pos] = 0
        return reference.gather(streams, len(payload), self.unit)

    def read(self, name: str) -> bytes:
        return self._holey(_key(name))

    def stored_shards(self, name: str) -> dict[int, bytes]:
        key = _key(name)
        shards = reference.encode(bytes(self.plan.payload(key, 0)),
                                  self.k, self.m, self.unit)
        return {pos: shards[pos] for pos, osd in
                enumerate(self.placement(name)) if osd is not None}

    def answer(self, op) -> bytes:
        return self._holey(op.key)


CONTROLS = {"stale_version": StaleVersion, "no_decode": NoDecode}


def compare_control(kind: str, plan, ops, dep, populated, sample) -> dict:
    """``verify.compare`` with the control standing where the program
    stood: its read-backs, its stores, its answers to the window's
    kept reads."""
    ctl = CONTROLS[kind](plan, ops, populated, dep.k, dep.m,
                         dep.stripe_unit, dep.placement)
    swapped = []
    for op in ops:
        if op.kind == "read" and op.answer is not None:
            op = copy.copy(op)
            op.answer = ctl.answer(op)
        swapped.append(op)
    return verify.compare(plan, swapped, ctl, dep.k, dep.m,
                          dep.stripe_unit, populated, sample)


def main(argv=None) -> int:
    from . import cells, run
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    result = run.execute(cell, args.seed, args.seconds, False,
                         control=True)
    ok = result["correct"] and not verify.is_correct(result["control"])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "program_correct": result["correct"],
        "control_correct": verify.is_correct(result["control"]),
        "program": result["compared"], "control": result["control"],
        "metrics": result["metrics"], "device": result["device"]}),
        flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
