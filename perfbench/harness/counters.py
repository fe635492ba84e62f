"""Window deltas of the program's counters and histograms (the
``lighthouse_tpu.utils.metrics`` registry): what the per-layer readers
and the run's own accounting read."""

from __future__ import annotations


def snapshot() -> dict:
    """``{family: {((label, value), ...): value}}``; a counter child reads
    its value, a histogram child ``(count, sum)``; gauges are left out
    (a gauge has no window delta)."""
    from lighthouse_tpu.utils import metrics

    out = {}
    for name, fam in metrics.registry_snapshot().items():
        if hasattr(fam, "children"):
            names = fam.labelnames
            items = fam.children().items()
        else:
            names, items = (), [((), fam)]
        rows = {}
        for key, child in items:
            if child.kind == "counter":
                rows[tuple(zip(names, key))] = child.value
            elif child.kind == "histogram":
                total, s, _ = child.snapshot()
                rows[tuple(zip(names, key))] = (total, s)
        if rows:
            out[name] = rows
    return out


class Delta:
    """``after - before`` of two snapshots, summed over the children
    whose labels match."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def _rows(self, snap, name, labels):
        for key, val in snap.get(name, {}).items():
            d = dict(key)
            if all(d.get(k) == str(v) for k, v in labels.items()):
                yield val

    def count(self, name: str, **labels) -> float:
        return (sum(self._rows(self.after, name, labels))
                - sum(self._rows(self.before, name, labels)))

    def hist(self, name: str, **labels):
        """(observations, sum) over the window."""
        a = list(self._rows(self.after, name, labels))
        b = list(self._rows(self.before, name, labels))
        return (sum(x[0] for x in a) - sum(x[0] for x in b),
                sum(x[1] for x in a) - sum(x[1] for x in b))
