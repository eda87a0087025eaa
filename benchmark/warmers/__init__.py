"""Warm-ups.  A traffic mix lists the ones its cell needs (`warm`); a
module offers `warm(dep, inflight)` (returns what it did, for the log)
and `NEEDS_DATA` (true: it runs after the generator's `prepare`, on
the mix's pre-written objects).

The program compiles lazily, per codec and per padded batch, on
background threads while the host serves; it has no call that says
"be ready for these shapes".  Until it has one (PERF.md, Open
questions), these modules - and `benchmark/pools/`, which knows how
objects lie on a store - are the only files of the benchmark that name
parts of the program beneath its entry points (`MiniCluster`, the
client, the admin socket's `perf dump` and `dump_historic_ops`,
`pg.scrub`, `ops.pipeline.stats` / `wait_warmups`, and
`ops.compile_cache`).  `benchmark/warmers/_ec.py` lists those parts."""
