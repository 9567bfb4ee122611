"""The router's imbalance over the experts held (model step layer): the
busiest held expert's routed copies over the mean of the held, per
layer and epoch from the program's epoch-record counters
(``moeBusiestCopies_l<i>`` over ``moeHeldCopies_l<i>`` / held), the
mean over the window's epochs and the layers. 100% is an even router;
the grouped product's padding and the worst row tile follow it."""

import statistics

from benchmark import work_sdar


def read(r):
    counters = r["facts"].get("moe_counters") or {}
    held = work_sdar.sizes(r["lm"])["held"]
    shares = [b * held / c
              for be, ce in zip(counters.get("busiest", []),
                                counters.get("copies", []))
              for b, c in zip(be, ce) if b == b and c == c and c > 0]
    if not shares:
        return None
    return 100.0 * statistics.fmean(shares)
