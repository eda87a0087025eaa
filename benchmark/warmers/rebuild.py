"""What a rebuild dispatches, at the cell's shapes: the one-loss decode
of a whole object for each of the k data positions (a lost parity
decodes nothing), and the synchronous re-encode of one object.  First
the readiness predicates the other warmers use (`_ec.py` lists the
names), then the rebuild's own two calls on a dummy object, so that
whatever they compile beyond those programs is compiled in set-up and
not in the window.

Beside what `_ec.py` names, of the program:
  osd.pg_repairing, perf dump osd.rebuild_full
        what `require_repair_state` asks for first
  osd.ecutil.decode_object, osd.ecutil.encode_object_ex
        the calls `pg._ec_read_local` and `osd._ec_push_shards` make
        for a rebuild (no HBM cache intent, as theirs have none)
  osd.ecutil.StripeInfo, osd.ecutil.fold_shard_crcs
        the stripe geometry and the per-shard CRC fold of a push
"""

from __future__ import annotations

from benchmark import cluster as cl
from benchmark.pools import ec
from benchmark.warmers import _ec

NEEDS_DATA = False


def require_repair_state(dep) -> None:
    """The cell needs a program that says when a repair is over and
    counts what it rebuilt (`osd.pg_repairing`, which `wait_for_clean`
    asks; the `rebuild_*` counters).  One without them ends set-up
    here, before anything compiles: it would report clean with shards
    still to rebuild, and the comparisons after clean would be made
    too early."""
    osd = next(iter(dep.cluster.osds.values()))
    block = osd.asok.execute("perf dump")["osd"]
    if not hasattr(osd, "pg_repairing") or "rebuild_full" not in block:
        raise cl.CheckFailed(
            "this program cannot run the cell: its OSDs have no "
            "pg_repairing() and no rebuild_* counters")


def warm(dep, inflight: int) -> dict:
    import jax
    from ceph_tpu.osd import ecutil
    require_repair_state(dep)
    k, m, unit = ec.shape(dep.config)
    S = ec.stripes_per_object(dep.config)
    buckets, devices = _ec.batch_buckets(dep, inflight), jax.devices()

    def probe(c) -> bool:
        one_loss = c._decode_rows([0], list(range(1, k + 1)))
        return all([c.backend.device_fn_if_ready(
            "bytes", one_loss, (), (b, k, unit), d) is not None
            and c.backend.fused_fn_if_ready(
                c.coding_matrix, (b, k, unit), d) is not None
            for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "rebuild fns")
    codec = _ec.codecs(dep)[0]
    sinfo = ecutil.StripeInfo(k, unit)
    payload = bytes(dep.object_bytes)
    shards, stripe_crcs = ecutil.encode_object_ex(codec, sinfo, payload)
    ecutil.fold_shard_crcs(stripe_crcs, unit)
    for lost in range(k):
        # the planned set of a rebuild: the k - 1 data shards left and
        # the first parity
        have = {p: shards[p] for p in range(k + 1) if p != lost}
        if ecutil.decode_object(codec, sinfo, have,
                                dep.object_bytes) != payload:
            raise RuntimeError(f"warm rebuild: position {lost} decoded "
                               "to other bytes")
    return {"rebuild_buckets": buckets, "stripes": S,
            "one_loss_positions": k, "waited_rebuild_s": round(waited, 3)}
