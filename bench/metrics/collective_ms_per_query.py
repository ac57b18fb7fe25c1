"""collective_ms_per_query: device time of the cross-chip all-reduces per
query.

Summed durations of the trace's ``XLA Ops`` events whose HLO opcode is
``all-reduce`` (or the ``all-reduce-start`` / ``all-reduce-done`` pair of
its asynchronous form), mean over the chips used, over the queries of the
traced slice of the window. In the sharded search these are the
reconcile's ``pmin`` of the incumbent and ``pmax`` of the continue flag in
every round, and the ``pmin``, ``pmax`` and ``psum`` after the last. Chips
run the rounds in lockstep, so an all-reduce also holds each chip's wait
for the slowest shard.

The opcode is read from the op's HLO text, not from its name: the v5e
compile of the sharded search names these ops after the collective JAX
asked for (``%pmin.27 = f32[1]{0:T(128)} all-reduce(%min.71), ...``,
``%pmax.31 = u32[]{...} all-reduce(...)``). ``None`` where the trace holds
no all-reduce: a one-chip program has none.
"""
from functools import lru_cache

from benchkit import trace as tr

OPCODES = ("all-reduce", "all-reduce-start", "all-reduce-done")


@lru_cache(maxsize=None)
def opcode(name: str, meta: str = "") -> str:
    """The HLO opcode of an op named ``name`` with string stats ``meta``:
    read from its HLO text, in the name or else the stats; where neither is
    HLO text, the name's stem (``all-reduce.3`` reads ``all-reduce``).
    Cached: a traced slice repeats a few hundred op names millions of
    times."""
    for text in (name, meta):
        short = tr.short_name(text.strip())
        if short != text.strip():  # HLO text: "name = shape opcode"
            return short.split()[-1]
    words = name.split()
    return words[0].lstrip("%").split(".")[0] if words else ""


def is_collective(ev) -> bool:
    return opcode(ev.name, ev.meta) in OPCODES


def read(run):
    if run.trace is None or not run.traced:
        return None
    # One pass over the events: the rest of the slice's millions are
    # dropped before any is clipped to the window.
    colls = [[e for e in ops if is_collective(e)] for ops in run.trace.devices]
    if not any(colls):
        return None
    lo, hi = run.trace.window
    per_chip = [sum(e.dur for e in tr.clip(ops, lo, hi)) for ops in colls]
    return sum(per_chip) / len(per_chip) / 1e6 / len(run.traced)
