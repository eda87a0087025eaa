"""Share of a wait, in percent, that the named legs of the way there
and back cover.

An op that waits for its peers (`replica_wait` of a write, `gather_wait`
of an EC read, `scrub.peer_wait` of a PG scrub, one a peer) waits for
the LAST answer.  Since the messenger stamps every frame at its sender
and every traced reply is a tracked op of kind `reply`, that answer's
way is a chain of spans on two docs under the waiting op's trace id:

  req.handoff    `msgr.handoff` of the request doc (the sub-op write,
                 the `sub_read`, the scan): the waiting op's thread
                 handing the request to its messenger's loop thread
  req.wire       its `msgr.wire`: encode, the frames queued ahead, the
                 socket, the receiver's loop getting to the read
  req.recv       its `msgr.recv` + `msgr.dispatch`
  req.op         the request doc from `mstart` to where it hands its
                 reply over (queue, execute, the store inside)
  reply.handoff  the same three of the `reply` doc, on the way back
  reply.wire
  reply.recv
  reply.queue    the reply's wait on the op shard (a write's replies)
  reply.execute  its handler, up to the wait's end (the wait closes
                 inside it where the handler completes the op)

For each root op with a span of the named wait the reader takes the
`reply` doc under its trace id, on the waiting doc's daemon, whose
`execute` begins last inside the wait (a gather completes before late
answers arrive: they begin after it), then the request doc that reply
answers (the daemon its description names after `<-`, the same shard
where the request names one, the last one begun before the reply was
handed over), and lays the legs on the wait
in that order, each clipped to the wait and to where the leg before it
ended.  A scrub's waits follow each other, one a peer: each is laid.
The metric is 100 x covered seconds / waited seconds over the window;
the log carries the mean of each leg per root op, in ms, which is the
table a `perf_opt` issue prices its lever by, and the mean `queued` of
the two `msgr.wire` spans (frames ahead on the connection).

Parameters:
  root_kind, root_match   the waiting docs, as `op_span_time` has them
  wait                    the wait span's name
  request_kinds           kinds of the request docs
  request_match           optional substring of their description

Where no doc of the window is of kind `reply` (a program from before
the kind existed) there is nothing to read.  A wait none of whose
answers left a doc counts as waited and not covered.
"""

from __future__ import annotations

LEGS = ("req.handoff", "req.wire", "req.recv", "req.op", "reply.handoff",
        "reply.wire", "reply.recv", "reply.queue", "reply.execute")


def _span(doc: dict, name: str) -> dict | None:
    """The last span of that name on a doc."""
    found = None
    for s in doc["spans"]:
        if s["name"] == name:
            found = s
    return found


def _way(doc: dict, side: str) -> list[tuple]:
    """(leg, t0, t1) of a doc's messenger spans; `msgr.recv` and
    `msgr.dispatch` are one leg."""
    out = []
    for leg, names in (("handoff", ("msgr.handoff",)),
                       ("wire", ("msgr.wire",)),
                       ("recv", ("msgr.recv", "msgr.dispatch"))):
        spans = [s for s in (_span(doc, n) for n in names) if s]
        if spans:
            out.append((f"{side}.{leg}", min(s["t0"] for s in spans),
                        max(s["t1"] for s in spans)))
    return out


def _first_stamp(doc: dict) -> float:
    return min([doc["mstart"]] + [s["t0"] for s in doc["spans"]])


def chain(reply: dict, requests: list[dict]) -> tuple[list, dict | None]:
    """The legs of one answer's way, in order, as (leg, t0, t1), and
    the request doc it answers, if the window has it."""
    left = _first_stamp(reply)
    what, sender = reply["description"].rstrip(")").rsplit(" <- ", 1)
    asked = [d for d in requests
             if d["daemon"] == sender and d["mstart"] <= left]
    # a `sub_read` names its shard, and an OSD may be asked for two
    shard = what.rsplit(" ", 1)[-1]
    asked = [d for d in asked
             if d["description"].endswith(f" {shard})")] or asked
    req = max(asked, key=lambda d: d["mstart"]) if asked else None
    legs: list[tuple] = []
    if req is not None:
        legs += _way(req, "req")
        legs.append(("req.op", req["mstart"],
                     min(req["mstart"] + req["duration"], left)))
    legs += _way(reply, "reply")
    for leg, name in (("reply.queue", "queue"),
                      ("reply.execute", "execute")):
        s = _span(reply, name)
        if s:
            legs.append((leg, s["t0"], s["t1"]))
    return legs, req


def lay(legs: list[tuple], w0: float, w1: float) -> dict:
    """Seconds of [w0, w1) each leg covers, a leg never reaching back
    over the one before it."""
    out: dict = {}
    at = w0
    for leg, t0, t1 in legs:
        a, b = max(t0, at), min(t1, w1)
        if b > a:
            out[leg] = out.get(leg, 0.0) + (b - a)
            at = b
    return out


def read(readings, params) -> float | None:
    docs = readings.op_docs
    # (trace id, daemon) -> [(when its `execute` began, reply doc)]
    replies: dict = {}
    for d in docs:
        ex = _span(d, "execute") if d["kind"] == "reply" else None
        if ex:
            replies.setdefault((d["trace_id"], d["daemon"]),
                               []).append((ex["t0"], d))
    if not replies:
        return None
    kinds = set(params["request_kinds"])
    match = params.get("request_match", "")
    requests: dict = {}
    for d in docs:
        if d["kind"] in kinds and match in d["description"]:
            requests.setdefault(d["trace_id"], []).append(d)
    waited = 0.0
    legs_s = dict.fromkeys(LEGS, 0.0)
    queued = {"req.wire": [], "reply.wire": []}
    roots, waits = set(), 0
    for d in docs:
        if d["kind"] != params["root_kind"] or not d["trace_id"] \
                or params["root_match"] not in d["description"]:
            continue
        for w in d["spans"]:
            if w["name"] != params["wait"]:
                continue
            roots.add(d["trace_id"])
            waits += 1
            waited += w["t1"] - w["t0"]
            inside = [(t, r) for t, r in replies.get(
                (d["trace_id"], d["daemon"]), ()) if w["t0"] <= t <= w["t1"]]
            if not inside:
                continue
            last = max(inside, key=lambda tr: tr[0])[1]
            legs, req = chain(last, requests.get(d["trace_id"], []))
            for leg, secs in lay(legs, w["t0"], w["t1"]).items():
                legs_s[leg] += secs
            for side, doc in (("req.wire", req), ("reply.wire", last)):
                wire = _span(doc, "msgr.wire") if doc else None
                if wire:
                    queued[side].append(wire["args"]["queued"])
    if not waits:
        return None
    covered = sum(legs_s.values())
    n = len(roots)
    table = ", ".join(f"{leg} {1000.0 * legs_s[leg] / n:.3f}"
                      for leg in LEGS)
    ahead = ", ".join(
        f"{side} {sum(v) / len(v):.2f}" for side, v in queued.items() if v)
    readings.log(
        f"wait legs of {params['wait']}: {waits} waits of {n} root ops, "
        f"{1000.0 * waited / n:.3f} ms an op waited, "
        f"{1000.0 * covered / n:.3f} ms covered, "
        f"{1000.0 * (waited - covered) / n:.3f} ms in no leg; "
        f"ms an op by leg: {table}; frames queued ahead: {ahead or 'none'}")
    if waited <= 0.0:
        return None
    return 100.0 * covered / waited
