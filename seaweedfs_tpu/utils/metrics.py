"""Minimal Prometheus-text metrics registry.

Counterpart of the reference's central registry (weed/stats/metrics.go:19-118)
— counters, gauges and duration histograms rendered in Prometheus exposition
format at /metrics, with optional label sets
(`count("read", labels={"collection": "c"})`,
`observe("read", dt, labels={"collection": "c"})`).
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

_BUCKETS = [0.0001, 0.001, 0.01, 0.1, 1.0, 10.0]

# Pluggable exemplar source: a zero-arg callable returning the ambient
# request's trace id ("" when none).  observe/ installs one at import
# time; keeping it injected (rather than importing observe here) keeps
# utils/ free of an upward dependency.  Exemplars let a p99 histogram
# bucket link straight to a concrete trace in /debug/trace.
_exemplar_source = None


def set_exemplar_source(fn) -> None:
    global _exemplar_source
    _exemplar_source = fn


def _escape(value) -> str:
    """Prometheus exposition label-value escaping: backslash, quote,
    newline (labels carry user-chosen collection names)."""
    return (str(value).replace("\\", r"\\").replace('"', r"\"")
            .replace("\n", r"\n"))


def _key(name: str, labels: dict | None) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{_escape(v)}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


class _Timer:
    """Context manager feeding Registry.observe — module-level so the
    per-request hot path never rebuilds a class object."""

    __slots__ = ("_registry", "_name", "_labels", "t0")

    def __init__(self, registry, name: str, labels: dict | None = None):
        self._registry = registry
        self._name = name
        self._labels = labels

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._registry.observe(self._name, time.perf_counter() - self.t0,
                               labels=self._labels)


class Registry:
    def __init__(self, subsystem: str):
        self.subsystem = subsystem
        self._lock = threading.Lock()
        self._counters: dict[str, float] = defaultdict(float)
        self._gauges: dict[str, float] = {}
        self._hist: dict[str, list[int]] = {}
        self._hist_sum: dict[str, float] = defaultdict(float)
        self._hist_count: dict[str, int] = defaultdict(int)
        # key -> per-bucket [(trace_id, seconds) | None]: the most recent
        # traced observation that landed in each bucket
        self._hist_ex: dict[str, list] = {}
        # family -> (label, {label value: [count, seconds]}): durations
        # kept as a count and a sum alone (add_seconds)
        self._seconds: dict[str, tuple[str, dict[str, list]]] = {}

    def count(self, name: str, value: float = 1.0,
              labels: dict | None = None) -> None:
        with self._lock:
            self._counters[_key(name, labels)] += value

    def gauge(self, name: str, value: float,
              labels: dict | None = None) -> None:
        with self._lock:
            self._gauges[_key(name, labels)] = value

    def observe(self, name: str, seconds: float,
                labels: dict | None = None) -> None:
        key = _key(name, labels)
        # read the trace id OUTSIDE the lock (contextvar, cheap, and a
        # misbehaving source callable must not run under our lock)
        trace = ""
        if _exemplar_source is not None:
            try:
                trace = _exemplar_source() or ""
            except Exception:
                trace = ""
        with self._lock:
            buckets = self._hist.setdefault(key, [0] * (len(_BUCKETS) + 1))
            for i, b in enumerate(_BUCKETS):
                if seconds <= b:
                    buckets[i] += 1
                    idx = i
                    break
            else:
                buckets[-1] += 1
                idx = len(_BUCKETS)
            self._hist_sum[key] += seconds
            self._hist_count[key] += 1
            if trace:
                ex = self._hist_ex.setdefault(
                    key, [None] * (len(_BUCKETS) + 1))
                ex[idx] = (trace, seconds)

    def add_seconds(self, name: str, label: str, entries) -> None:
        """Durations of family `name`_seconds that is kept as `_count`
        and `_sum` alone (a summary without quantiles), one series per
        value of its one `label`: each of `entries` starts (label value,
        seconds). Any number of them under one lock and with no key to
        format: for a path too hot for a histogram's buckets and
        exemplars, which nothing reads for such a family."""
        with self._lock:
            fam = self._seconds.get(name)
            if fam is None:
                fam = self._seconds[name] = (label, {})
            totals = fam[1]
            for entry in entries:
                rec = totals.get(entry[0])
                if rec is None:
                    totals[entry[0]] = [1, entry[1]]
                else:
                    rec[0] += 1
                    rec[1] += entry[1]

    def timed(self, name: str, labels: dict | None = None):
        return _Timer(self, name, labels)

    def value(self, name: str, labels: dict | None = None,
              default: float = 0.0) -> float:
        """Current value of a counter or gauge — for tests and code that
        branches on its own counters (e.g. cache hit-rate probes)
        without re-parsing the exposition text."""
        key = _key(name, labels)
        with self._lock:
            if key in self._counters:
                return self._counters[key]
            return self._gauges.get(key, default)

    def snapshot(self, prefix: str = "") -> dict[str, float]:
        """Current counters + gauges as a flat {rendered_key: value}
        dict, optionally filtered by family-name prefix — the JSON face
        of the registry for admin status endpoints (ec.mesh.status)
        that must not re-parse exposition text."""
        with self._lock:
            out: dict[str, float] = {}
            for key, v in self._counters.items():
                if key.startswith(prefix):
                    out[key] = v
            for key, v in self._gauges.items():
                if key.startswith(prefix):
                    out[key] = v
            return out

    def exemplars(self, name: str,
                  labels: dict | None = None) -> list:
        """Per-bucket [(trace_id, seconds) | None] for one histogram —
        bucket i covers observations <= _BUCKETS[i], the last entry is
        the +Inf overflow.  Empty list when the histogram has never seen
        a traced observation."""
        key = _key(name, labels)
        with self._lock:
            ex = self._hist_ex.get(key)
            return list(ex) if ex else []

    @staticmethod
    def _split(key: str) -> tuple[str, str]:
        """'read{a="b"}' -> ('read', '{a="b"}')."""
        if "{" in key:
            name, _, rest = key.partition("{")
            return name, "{" + rest
        return key, ""

    @classmethod
    def _families(cls, keys) -> dict[str, list[str]]:
        """Group metric keys by family name, families and label sets both
        sorted — exposition format requires all samples of one family to
        be contiguous under a single # TYPE line."""
        fams: dict[str, list[str]] = {}
        for key in sorted(keys):
            fams.setdefault(cls._split(key)[0], []).append(key)
        return dict(sorted(fams.items()))

    def render(self, exemplars: bool = False) -> str:
        """Prometheus exposition text.  ``exemplars=True`` appends the
        OpenMetrics ``# {trace_id="..."} value`` exemplar suffix to each
        histogram bucket that has one (served at /metrics?exemplars=1 —
        off by default because plain-Prometheus scrapers reject it)."""
        with self._lock:
            lines = []
            p = f"seaweedfs_tpu_{self.subsystem}"
            # _families groups each kind's keys by unique family name, so
            # one # TYPE line at the top of each family iteration is
            # exactly once per family (the old flat-key loop needed a
            # seen-set that mixed str and tuple entries)
            for name, keys in self._families(self._counters).items():
                lines.append(f"# TYPE {p}_{name}_total counter")
                for key in keys:
                    _, lbl = self._split(key)
                    lines.append(f"{p}_{name}_total{lbl} "
                                 f"{self._counters[key]}")
            for name, keys in self._families(self._gauges).items():
                lines.append(f"# TYPE {p}_{name} gauge")
                for key in keys:
                    _, lbl = self._split(key)
                    lines.append(f"{p}_{name}{lbl} {self._gauges[key]}")
            for name, keys in self._families(self._hist).items():
                lines.append(f"# TYPE {p}_{name}_seconds histogram")
                for key in keys:
                    _, lbl = self._split(key)
                    # merge the key's labels with the per-bucket le label
                    inner = lbl[1:-1] + "," if lbl else ""
                    buckets = self._hist[key]
                    ex = (self._hist_ex.get(key)
                          if exemplars else None) or []
                    acc = 0
                    for i, b in enumerate(_BUCKETS):
                        acc += buckets[i]
                        line = (f"{p}_{name}_seconds_bucket"
                                f'{{{inner}le="{b}"}} {acc}')
                        if i < len(ex) and ex[i]:
                            line += (f' # {{trace_id="{ex[i][0]}"}}'
                                     f" {ex[i][1]}")
                        lines.append(line)
                    acc += buckets[-1]
                    line = (f"{p}_{name}_seconds_bucket"
                            f'{{{inner}le="+Inf"}} {acc}')
                    if len(ex) > len(_BUCKETS) and ex[-1]:
                        line += (f' # {{trace_id="{ex[-1][0]}"}}'
                                 f" {ex[-1][1]}")
                    lines.append(line)
                    lines.append(f"{p}_{name}_seconds_sum{lbl} "
                                 f"{self._hist_sum[key]}")
                    lines.append(f"{p}_{name}_seconds_count{lbl} "
                                 f"{self._hist_count[key]}")
            for name, (label, totals) in sorted(self._seconds.items()):
                lines.append(f"# TYPE {p}_{name}_seconds summary")
                for value, (count, seconds) in sorted(totals.items()):
                    lbl = f'{{{label}="{_escape(value)}"}}'
                    lines.append(f"{p}_{name}_seconds_sum{lbl} {seconds}")
                    lines.append(f"{p}_{name}_seconds_count{lbl} {count}")
            return "\n".join(lines) + "\n"

    def is_empty(self) -> bool:
        with self._lock:
            return not (self._counters or self._gauges or self._hist
                        or self._seconds)


# --- process-wide shared registries ---
# Subsystems that are not servers (the EC feed governor, background
# maintenance) publish through whichever server process hosts them: they
# register here and every server's /metrics handler appends
# render_shared() to its own registry's exposition text. Family names
# can't collide across registries because each subsystem gets its own
# seaweedfs_tpu_<subsystem>_ prefix.

_shared: dict[str, "Registry"] = {}
_shared_lock = threading.Lock()


def shared(subsystem: str) -> "Registry":
    """The process-wide registry for `subsystem` (created on first use)."""
    with _shared_lock:
        reg = _shared.get(subsystem)
        if reg is None:
            reg = _shared[subsystem] = Registry(subsystem)
        return reg


def exposition(registry: "Registry", request) -> str:
    """The full /metrics body for one server: its own registry plus the
    shared subsystem registries, with OpenMetrics exemplars when the
    scrape asks for them (?exemplars=1)."""
    ex = request.query.get("exemplars", "") in ("1", "true")
    return registry.render(exemplars=ex) + render_shared(exemplars=ex)


def render_shared(exemplars: bool = False) -> str:
    """Exposition text of every non-empty shared registry, stable order."""
    with _shared_lock:
        regs = [_shared[name] for name in sorted(_shared)]
    return "".join(r.render(exemplars=exemplars)
                   for r in regs if not r.is_empty())
