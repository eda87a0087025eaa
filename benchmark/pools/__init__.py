"""Pool kinds.  A configuration names one of these modules
(`pool_kind`); a module offers `create(dep, name)` (make the pool
through the client's admin calls), `stored(dep, oid)` (what every
acting OSD's store holds of one object, position by position, for the
comparison with the configuration's reference), `corrupt(dep, oid)`
(damage one stored copy under the store; returns the PG and the name a
scrub must flag) and `file_bytes(config)` (bytes of one stored file a
scrub verifies)."""
