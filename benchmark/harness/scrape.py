"""One parse of a Prometheus text page."""

from __future__ import annotations

import re

_SERIES = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


class Scrape:
    def __init__(self, text: str = ""):
        self.series = []
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            m = _SERIES.match(line)
            if m:
                labels = dict(_LABEL.findall(m.group(2) or ""))
                try:
                    self.series.append((m.group(1), labels, float(m.group(3))))
                except ValueError:
                    continue

    def total(self, name: str, labels: dict | None = None) -> float:
        """Sum of the series of that name whose labels include `labels`."""
        want = (labels or {}).items()
        return sum(
            v for n, lb, v in self.series
            if n == name and all(lb.get(k) == x for k, x in want)
        )

    def by_label(self, name: str, key: str) -> dict:
        out: dict = {}
        for n, labels, v in self.series:
            if n == name:
                out[labels.get(key, "")] = out.get(labels.get(key, ""), 0) + v
        return out

    def labels_of(self, name: str) -> dict:
        return next((lb for n, lb, _ in self.series if n == name), {})

