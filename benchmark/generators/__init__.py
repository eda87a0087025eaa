"""Traffic generators.  A traffic mix (`benchmark/traffic/<name>.json`)
names one of these modules and gives it parameters; a module offers
`prepare(ctx)` (set-up the mix needs: pre-writes, failed OSDs),
`run(ctx, seconds)` (ramp, then the measured window; returns `t_open`,
`t_close`, `ramp_s`, `ops` as (kind, t0, t1, ok, bytes), `bad` and
`errors`) and `verify(ctx, window)` (once the window closed: the
`comparisons` made, the `stored_objects` as (key, version) whose
stored state the harness compares with the configuration's reference,
and optionally `after_stored_check`, run after that comparison)."""
