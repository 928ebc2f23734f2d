"""The shift the device's timestamps need to obey causality in the traced
window, us: with lo = max over the steps of (dispatch start - module start)
and hi = min of (wait end - module end), 0 where lo <= 0 <= hi, else the size
of the nearer of the two.  What anything that sets a host timestamp beside
a device's (the span that names an idle gap in `breakdown.idle_gaps`) is
good to in this run (kind train).

A floor and a validity flag, nothing to lower: 0 says the trace is consistent
as it stands, not that the clocks are aligned.  Where the bounds leave more
room than the shift (hi - lo is 3.0-3.2 ms on `transformer-train-dp4`, the
shift ~1 ms) a shifted run reads 0 too; `python3
benchmark/harness/turnaround.py` prints lo, hi and hi - lo, which tell such
runs apart."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.clock_skew_us(obs)
