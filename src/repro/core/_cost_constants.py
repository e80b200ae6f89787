"""Measured per-row costs of the streaming engine's primitives.

AUTOGENERATED by ``make calibrate`` (benchmarks/calibrate.py) — do not
edit by hand; re-run calibration on new hardware instead.  The ``cpu``
entry is committed so :mod:`repro.core.cost_model` always has a floor
to stand on; other backends are added by running ``make calibrate`` on
a machine that has them.

All figures are nanoseconds per input row.  ``absorb_row_ns`` is the
end-to-end per-row cost of one streamed absorb step under each
run-generation policy (including the batch sort that policy pays);
``sort_row_ns`` is the isolated cost of that batch sort, so a caller
with pre-sorted input can subtract it (the "credit sorted input with a
zero sort term" rule in :func:`repro.core.cost_model.policy_cost_per_row`).
"""

COST_SCHEMA_VERSION = 1

COST_CONSTANTS = {'cpu': {'absorb_dup_row_ns': {'early_agg': 2153.875,
                               'inrun_dedup': 1969.199,
                               'rs': 4484.207,
                               'traditional': 1321.41},
         'absorb_row_ns': {'early_agg': 2318.234,
                           'inrun_dedup': 2079.238,
                           'rs': 5498.202,
                           'traditional': 1402.548},
         'hash_probe_row_ns': 421.773,
         'merge_row_ns': 165.798,
         'meta': {'backend': 'cpu',
                  'dispatch_backend': 'xla',
                  'generated_by': 'benchmarks/calibrate.py',
                  'note': 'memory_rows=4096 batch_rows=1024 n_rows=65536 '
                          'iters=3',
                  'smoke': False},
         'schema_version': 1,
         'sort_row_ns': 43.155,
         'spill_write_row_ns': 16.976},
 'tpu': {'absorb_dup_row_ns': {'early_agg': 1158.375,
                               'inrun_dedup': 227.814,
                               'rs': 5175.396,
                               'traditional': 55.364},
         'absorb_row_ns': {'early_agg': 1169.742,
                           'inrun_dedup': 226.649,
                           'rs': 6263.828,
                           'traditional': 53.407},
         'hash_probe_row_ns': 224.672,
         'merge_row_ns': 422.357,
         'meta': {'backend': 'tpu',
                  'dispatch_backend': 'xla',
                  'generated_by': 'benchmarks/calibrate.py',
                  'note': 'memory_rows=524288 batch_rows=524288 '
                          'n_rows=2097152 iters=2',
                  'smoke': False},
         'schema_version': 1,
         'sort_row_ns': 2.216,
         'spill_write_row_ns': 1.534}}
