"""BAD fixture: metrics emitted into governed families without a
families.py registration (or with the wrong kind)."""
from incubator_mxnet_tpu.profiler.counters import (counter, histogram,
                                                   observe, set_gauge)

counter("healthmon.not_a_real_metric", "healthmon").increment()
histogram("memscope.invented_histogram", "memscope")
observe("perfscope.mfu", 0.5, "perfscope")       # mfu is a gauge
set_gauge("resilience.rollbacks", 1, "resilience")   # a counter
