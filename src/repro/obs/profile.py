"""Stage-level profiling: per-stage latency histograms and cost-model
drift accounting.

Device timing caveat (why stages are *host-side spans at sync
boundaries*): the pruned query path fuses decode+score inside one jitted
computation, so the only honest host-visible seams are data staging
(host→device transfer), kernel execution (closed by
``block_until_ready`` via ``stage(...).sync(x)``), and result fetch
(device→host). Stage names are dotted paths — ``planner.probe``,
``device.kernel``, ``serve.score`` — and land in fixed log-bucket
:class:`repro.serving.Histogram`\\ s, exported through
``Metrics.register_histogram_provider`` as
``service_stage_latency_seconds{stage=...}``.
"""

from __future__ import annotations

import math
import threading

from repro.serving.histogram import Histogram

__all__ = ["StageProfiler", "CostDrift"]


class StageProfiler:
    """Latency histogram per named stage, created on first observation.

    Thread-safe; designed to be attached alongside a trace via
    ``obs.attach(trace, profiler)`` so ``obs.stage(...)`` blocks feed it
    without plumbing. ``histograms()`` is the live view a Metrics
    histogram-family provider samples at render time. Tallies
    (``obs.tally``: a served batch's route, the blocks its probe saw)
    share the table, so ``stages()`` carries them too.
    """

    def __init__(self, bounds=None):
        self._bounds = bounds
        self._stages: dict[str, Histogram] = {}
        self._tallies: set[str] = set()
        self._lock = threading.Lock()

    def observe(self, name: str, seconds: float) -> None:
        h = self._stages.get(name)
        if h is None:
            with self._lock:
                h = self._stages.setdefault(
                    name, Histogram(self._bounds) if self._bounds is not None
                    else Histogram())
        h.observe(seconds)

    def tally(self, name: str, amount: float) -> None:
        """Count one event with an amount (``obs.tally``): kept beside
        the stages, so ``stages()`` carries it as (count, sum), but it
        is no latency and stays out of ``histograms()``."""
        if name not in self._tallies:
            with self._lock:
                self._tallies.add(name)
        self.observe(name, amount)

    def histogram(self, name: str) -> Histogram | None:
        return self._stages.get(name)

    def histograms(self) -> dict[str, Histogram]:
        """{prometheus labels string: Histogram} for a metrics provider:
        the stage latencies, tallies left out."""
        with self._lock:
            return {f'stage="{k}"': h for k, h in self._stages.items()
                    if k not in self._tallies}

    def stages(self) -> dict[str, Histogram]:
        """Every histogram by name, tallies included."""
        with self._lock:
            return dict(self._stages)

    def tallies(self) -> dict[str, tuple[int, float]]:
        """(events, summed amount) per tally name."""
        with self._lock:
            hs = [(n, self._stages.get(n)) for n in self._tallies]
        out = {}
        for name, h in hs:
            if h is not None:
                counts, total = h.snapshot()
                out[name] = (int(counts.sum()), total)
        return out

    def snapshot(self) -> dict[str, dict]:
        """Summary per stage: count / mean / p50 / p99 (seconds)."""
        out = {}
        for name, h in self.stages().items():
            if name in self._tallies:
                continue
            out[name] = {
                "count": h.count,
                "mean_s": h.mean,
                "p50_s": h.quantile(0.5),
                "p99_s": h.quantile(0.99),
            }
        return out


class CostDrift:
    """Predicted-vs-actual cost ratio across serve flushes.

    The planner's cost model speaks abstract units; calibration
    (``fit_query_constants``) stores ``seconds_per_unit`` so predicted
    units convert to predicted seconds. Without an installed
    calibration the converter self-fits from the accumulated
    (units, seconds) totals — the gauge then measures *consistency* of
    the model's ranking rather than absolute accuracy, which is exactly
    what plan decisions depend on.

    ``drift`` is last-flush predicted_seconds / measured_seconds:
    1.0 = perfectly calibrated, >1 = model over-estimates cost.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.total_units = 0.0
        self.total_seconds = 0.0
        self.flushes = 0
        self.last_ratio = float("nan")

    @staticmethod
    def _calibrated_seconds_per_unit() -> float | None:
        try:
            from repro.core import cost_model

            cal = cost_model.calibration()
            if cal:
                spu = cal.get("fit", {}).get("seconds_per_unit")
                if spu:
                    return float(spu)
        except Exception:
            pass
        return None

    def seconds_per_unit(self) -> float | None:
        spu = self._calibrated_seconds_per_unit()
        if spu is not None:
            return spu
        with self._lock:
            if self.total_units > 0 and self.total_seconds > 0:
                return self.total_seconds / self.total_units
        return None

    def record(self, predicted_units: float, measured_seconds: float) -> float:
        """Fold in one flush; returns the flush's drift ratio (NaN until
        a converter exists or for non-finite inputs)."""
        if (not math.isfinite(predicted_units) or predicted_units <= 0
                or not math.isfinite(measured_seconds)
                or measured_seconds <= 0):
            return float("nan")
        spu = self._calibrated_seconds_per_unit()
        with self._lock:
            if spu is None and self.total_units > 0:
                spu = self.total_seconds / self.total_units
            self.total_units += predicted_units
            self.total_seconds += measured_seconds
            self.flushes += 1
            if spu is None:
                return float("nan")
            self.last_ratio = (predicted_units * spu) / measured_seconds
            return self.last_ratio

    @property
    def drift(self) -> float:
        """Gauge value: last flush's predicted/actual ratio (0.0 until
        the first measurable flush — Prometheus gauges can't be NaN)."""
        r = self.last_ratio
        return r if math.isfinite(r) else 0.0

