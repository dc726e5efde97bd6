"""Prometheus text exposition with the stdlib alone (the CI image needs no
prometheus_client): the histogram and the label / info-gauge / labelled-
counter helpers that the serving metrics (serve/metrics.py) and the
trainer's telemetry (train/telemetry.py) both render with.

Histograms keep BOTH Prometheus cumulative bucket counts (cheap, mergeable,
what scrapers want) and a capped reservoir of raw samples so the bench leg
reports exact p50/p99 instead of bucket-edge estimates (exact until
`max_samples` observations; the cap only bounds memory on a long-lived
server — CI/bench runs never reach it).
"""

from __future__ import annotations

from typing import Optional

# Decode SLOs span ~1 ms (one fused step) to minutes (a queued long
# prompt), so the default grid is log-ish across that range, in seconds.
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"')


def _labels(labels: dict) -> str:
    """Render a label dict as `{k="v",...}` (empty dict -> "")."""
    if not labels:
        return ""
    return "{" + ",".join(f'{k}="{_esc(v)}"'
                          for k, v in labels.items()) + "}"


def _render_info(name: str, help_: str, info: dict) -> list[str]:
    """Prometheus info-gauge idiom: constant 1 with the facts as labels."""
    if not info:
        return []
    labels = ",".join(f'{k}="{_esc(v)}"' for k, v in sorted(info.items()))
    return [f"# HELP {name} {help_}", f"# TYPE {name} gauge",
            f"{name}{{{labels}}} 1"]


def render_families(families: dict) -> list[str]:
    """Labelled counters read live at render time (`register_family` of
    ServeMetrics and TrainMetrics): name -> (label, fn, help), `fn()` =
    {label value: number}."""
    lines: list[str] = []
    for name, (label, fn, help_) in sorted(families.items()):
        lines += [f"# HELP {name} {help_}", f"# TYPE {name} counter"]
        try:
            series = fn()
        except Exception:  # pragma: no cover — source died mid-shutdown
            continue
        for value, n in sorted(series.items()):
            lines.append(f"{name}{_labels({label: value})} {n}")
    return lines


class Histogram:
    """Prometheus-style cumulative histogram + exact quantiles."""

    def __init__(self, name: str, help_: str,
                 buckets=LATENCY_BUCKETS, max_samples: int = 65536):
        self.name = name
        self.help = help_
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0
        self._samples: list[float] = []
        self._max_samples = max_samples

    def observe(self, v: float) -> None:
        self.sum += v
        self.count += 1
        for i, edge in enumerate(self.buckets):
            if v <= edge:
                self.counts[i] += 1
                break
        else:
            self.counts[-1] += 1
        if len(self._samples) < self._max_samples:
            self._samples.append(v)

    def quantile(self, q: float) -> Optional[float]:
        """Exact quantile over the retained samples (None when empty)."""
        if not self._samples:
            return None
        s = sorted(self._samples)
        idx = min(len(s) - 1, max(0, round(q * (len(s) - 1))))
        return s[idx]

    @property
    def max(self) -> Optional[float]:
        return max(self._samples) if self._samples else None

    def count_le(self, threshold: float) -> int:
        """Observations provably <= threshold from the bucket counts
        alone (cumulative count of every bucket whose edge fits). Exact
        when the threshold is a bucket edge — SLO targets default to
        edges of LATENCY_BUCKETS for exactly this reason — and a
        conservative undercount otherwise."""
        total = 0
        for edge, c in zip(self.buckets, self.counts):
            if edge <= threshold:
                total += c
            else:
                break
        return total

    def to_dict(self) -> dict:
        """JSON-serializable snapshot carrying everything `merge_from`
        needs: per-bucket (non-cumulative) counts merge by elementwise
        addition, reservoirs by concatenate-and-cap."""
        return {"name": self.name, "help": self.help,
                "buckets": list(self.buckets),
                "counts": list(self.counts),
                "sum": self.sum, "count": self.count,
                "samples": list(self._samples)}

    def merge_from(self, snap: dict) -> None:
        """Fold another process's `to_dict()` snapshot into this
        histogram. Bucket grids must match exactly — merging histograms
        with different edges would silently misbucket, so it raises."""
        if tuple(snap["buckets"]) != self.buckets:
            raise ValueError(
                f"{self.name}: bucket mismatch "
                f"({snap['buckets']!r} != {list(self.buckets)!r})")
        for i, c in enumerate(snap["counts"]):
            self.counts[i] += int(c)
        self.sum += float(snap["sum"])
        self.count += int(snap["count"])
        room = self._max_samples - len(self._samples)
        if room > 0:
            self._samples.extend(snap["samples"][:room])

    @classmethod
    def from_dict(cls, snap: dict,
                  max_samples: int = 65536) -> "Histogram":
        h = cls(snap["name"], snap.get("help", ""),
                buckets=snap["buckets"], max_samples=max_samples)
        h.merge_from(snap)
        return h

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} histogram"]
        cum = 0
        for edge, c in zip(self.buckets, self.counts):
            cum += c
            lines.append(f'{self.name}_bucket{{le="{edge}"}} {cum}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {self.count}')
        lines.append(f"{self.name}_sum {self.sum}")
        lines.append(f"{self.name}_count {self.count}")
        return lines

    def summary(self, unit: str = "ms", scale: float = 1e3) -> dict:
        """p50/p99/max/mean for the bench leg JSON — milliseconds by
        default; token-valued histograms pass unit='tok', scale=1."""
        if not self.count:
            return {"count": 0}
        return {"count": self.count,
                f"p50_{unit}": round((self.quantile(0.50) or 0.0) * scale, 3),
                f"p99_{unit}": round((self.quantile(0.99) or 0.0) * scale, 3),
                f"max_{unit}": round((self.max or 0.0) * scale, 3),
                f"mean_{unit}": round(self.sum / self.count * scale, 3)}
