"""Faults planted under the timed path, and the control.

Each entry of ``PLANTS`` patches the program in this process
(``run.main(..., plant=PLANTS[name])``), so a run drives the real loop
with one thing broken: the control before the daemon starts (its shorter
fuse is another program, compiled in the warm-up), every fault as the
window opens (a fault that stops placements would otherwise stall the
warm-up). The benchmark's own runs plant nothing; ``control.py`` and
``tests/test_faults.py`` do.

- ``early_stop`` (the control): the auction stops after
  ``CONTROL_FUSE`` rounds and its uncertified result is accepted as if
  it had converged. It breaks the guarantee every configuration states,
  an exact least-cost placement each round, in the way a later change
  cutting auction rounds for latency would.
- ``stale``: every solve returns the state unchanged, no pod placed.
- ``half``: half the pending pods (fixed by name) are left out of every
  round's input.
- ``altered``: one placement per round is moved, where the solver
  produces it, to the machine with the fewest free seats.
- ``misbound``: one binding per POST batch goes to another node than
  the one decided.
- ``unseen``: observe loses one pending pod in sixteen (fixed by name):
  the rounds never see it.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

CONTROL_FUSE = 2


def early_stop(fuse: int = CONTROL_FUSE) -> None:
    from poseidon_tpu.ops import resident

    chain = resident._resident_chain

    def stopped(*args, **kw):
        kw["max_rounds"] = fuse
        out = chain(*args, **kw)
        # "converged", computed on the device (no host transfer, which
        # the round's transfer guard forbids)
        return out[:4] + (out[4] == out[4],) + out[5:]

    resident._resident_chain = stopped


def _patch_outcome(change) -> None:
    from poseidon_tpu.ops import resident

    finish = resident.ResidentSolver.finish_round

    def patched(self, *args, **kw):
        out = finish(self, *args, **kw)
        if getattr(out, "assignment", None) is None:
            return out
        return change(out)

    resident.ResidentSolver.finish_round = patched


def stale() -> None:
    _patch_outcome(lambda out: dataclasses.replace(
        out, assignment=np.full_like(out.assignment, -1)))


def altered() -> None:
    def change(out):
        asg = np.array(out.assignment)
        placed = np.flatnonzero(asg >= 0)
        slots = getattr(out.topology, "slots", None)
        if placed.size == 0 or slots is None:
            return out
        free = np.asarray(slots, np.int64)[: int(asg.max()) + 1].copy()
        free -= np.bincount(asg[placed], minlength=free.size)
        t = int(placed[0])
        free[asg[t]] = np.iinfo(np.int64).max   # not its own machine
        asg[t] = int(np.argmin(free))
        return dataclasses.replace(out, assignment=asg)

    _patch_outcome(change)


def half() -> None:
    from poseidon_tpu.bridge import bridge as bridge_mod

    state = bridge_mod.SchedulerBridge.cluster_state

    def dropped(self):
        cs = state(self)
        cs.tasks = [t for t in cs.tasks
                    if getattr(t.phase, "value", t.phase) != "Pending"
                    or zlib.crc32(t.uid.encode()) % 2]
        return cs

    bridge_mod.SchedulerBridge.cluster_state = dropped


def misbound() -> None:
    from poseidon_tpu import cli

    post = cli._post_bindings

    def moved(client, bridge, bindings, *args, **kw):
        if bindings:
            machines = sorted(bridge.machines)
            first = next(iter(bindings))
            m = bindings[first]
            bindings = dict(bindings)
            bindings[first] = machines[(machines.index(m) + 1)
                                       % len(machines)]
        return post(client, bridge, bindings, *args, **kw)

    cli._post_bindings = moved


def unseen() -> None:
    from poseidon_tpu.bridge import bridge as bridge_mod

    observe = bridge_mod.SchedulerBridge.observe_pods

    def lossy(self, pods, *args, **kw):
        pods = [t for t in pods
                if getattr(t.phase, "value", t.phase) != "Pending"
                or zlib.crc32(t.uid.encode()) % 16]
        return observe(self, pods, *args, **kw)

    bridge_mod.SchedulerBridge.observe_pods = lossy


@dataclasses.dataclass(frozen=True)
class Plant:
    patch: object
    at_window: bool     # False: before the daemon starts


PLANTS = {
    "early_stop": Plant(early_stop, at_window=False),
    "stale": Plant(stale, at_window=True),
    "half": Plant(half, at_window=True),
    "altered": Plant(altered, at_window=True),
    "misbound": Plant(misbound, at_window=True),
    "unseen": Plant(unseen, at_window=True),
}
