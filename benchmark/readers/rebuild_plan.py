"""What the window's rebuilds planned and gathered, from the args of
their `rebuild.read` spans (`osd/recovery_svc.py` `_ec_rebuild`):
`planned`, the chunks the first plan named; `widened`, 1 where that
plan's gather did not give the shard and a second gather of every
other holder was made; `chunks`, the chunks the last gather had in
hand.  The docs are `recovery_ops`'s (one a rebuilt object and
position); a rebuild counts where it pushed.

Parameters:
  what      widened_share   rebuilds with `widened` 1 over the rebuilds
                            whose read carries `widened`
            planned_chunks  mean `planned` over the rebuilds whose read
                            carries it
            gather_chunks   mean `chunks` over the rebuilds that read
                            anything (a cache-served one read nothing)

A program from before `planned` and `widened` has nothing to read for
the first two; its `chunks` are there, and a rebuild of its that had 9
or more in hand had widened where no plan of the code names more than
k.
"""

from __future__ import annotations

from benchmark.readers.recovery_ops import rebuild_docs, spans_named


def reads_of_pushed(docs: list[dict]) -> list[dict]:
    """The args of the `rebuild.read` span of every rebuild doc that
    has an acknowledged push."""
    out = []
    for d in rebuild_docs(docs):
        if any(s.get("args", {}).get("acked", True)
               for s in spans_named([d], "rebuild.push")):
            out += [s.get("args") or {}
                    for s in spans_named([d], "rebuild.read")]
    return out


def read(readings, params) -> float | None:
    reads = reads_of_pushed(readings.op_docs)
    what = params["what"]
    if what == "gather_chunks":
        had = [a["chunks"] for a in reads if a.get("chunks")]
        if not had:
            return None
        hist: dict = {}
        for n in had:
            hist[n] = hist.get(n, 0) + 1
        readings.log(f"rebuild gathers by chunks in hand "
                     f"{dict(sorted(hist.items()))}")
        return sum(had) / len(had)
    if what == "widened_share":
        told = [a["widened"] for a in reads if "widened" in a]
        if not told:
            return None
        readings.log(f"rebuilds widened: {sum(told)} of {len(told)}")
        return sum(told) / len(told)
    if what == "planned_chunks":
        told = [a["planned"] for a in reads if "planned" in a]
        return sum(told) / len(told) if told else None
    raise KeyError(f"unknown rebuild plan reading {what!r}")
