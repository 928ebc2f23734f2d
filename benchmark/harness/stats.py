"""Arithmetic of the yardstick: percentiles, the gap between tokens, the
loss check, and the count of compilations."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule) in plain
    Python; q in [0, 100]."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def tpot_s(admitted_at: float, ttft_s: float, finished_at: float,
           n_tokens: int) -> float:
    """The gap between tokens one caller saw: the time after the first
    token over the tokens after the first.  Stalls behind later
    admissions' prefills are in it."""
    if n_tokens < 2:
        raise ValueError("a gap between tokens needs two tokens")
    return (finished_at - admitted_at - ttft_s) / (n_tokens - 1)


def bf16_step(x: float) -> float:
    """The distance between neighbouring bfloat16 values at |x| (8 bits of
    significand): 0.0625 near 10."""
    return 2.0 ** (math.floor(math.log2(abs(x))) - 7) if x else 0.0


def quarter_means(losses) -> tuple:
    """(mean of the first quarter, mean of the last quarter)."""
    q = max(1, len(losses) // 4)
    return sum(losses[:q]) / q, sum(losses[-q:]) / q


def loss_fell(losses, start=None) -> bool:
    """Every loss finite, and the loss fell.

    With `start`, the loss before any step was taken: the mean of the last
    quarter is below `start`, and not above the mean of the first quarter by
    more than one step of bfloat16 there.  The program fetches its loss in
    bf16, whose steps near 10 are 0.0625, and the four-chip Transformer cell
    falls by about one such step over a whole window (10.25 to 10.1875 in 75
    steps, PERF.md 6), so its two quarters can read the same number while
    the loss falls; from `start` it has fallen by three steps or more.  With
    under 8 steps (a rehearsal's window under load) the quarters are single
    steps, which a few steps of momentum can put in either order: then only
    `start` is held.

    Without `start`: the last quarter's mean strictly below the first's."""
    if not losses or not all(math.isfinite(v) for v in losses):
        return False
    n = len(losses)
    first, last = quarter_means(losses)
    if start is None:
        return n < 2 or last < first
    if not math.isfinite(start) or not last < start:
        return False
    return n < 8 or last <= first + bf16_step(first)


class CompileCounter:
    """Executables built, or loaded from the persistent cache, counted from
    jax's own monitoring events (as chip_smoke.py's)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1
            self.seconds += duration
