"""Per-layer metric readers.  A metric (`benchmark/layer_metrics/
<name>.json`) names one of these modules and gives it parameters; a
module offers `read(readings, params)` and returns the number, or None
when it finds nothing to read (the harness then leaves the metric out
of the line)."""
