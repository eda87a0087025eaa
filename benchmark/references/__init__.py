"""Plain references.  A configuration names one of these modules
(`reference`); a module offers `stored(payload, config)`: what the
pool's acting OSDs have to hold of an object with that payload, as
(bytes, crc or None) position by position, in the order the
configuration's pool module lists them.  A reference imports nothing
of the program and takes nothing the program has made."""
