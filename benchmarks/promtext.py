"""The little of the Prometheus text format the benchmark reads."""

from __future__ import annotations


def parse(text: str) -> list:
    """-> [(series name, label text, value)] of every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, labels = head.partition("{")
        try:
            out.append((name, labels.rstrip("}"), float(value)))
        except ValueError:
            continue
    return out


def total(samples: list, name: str, labels: str = "") -> float:
    """Sum of the series ``name`` whose label text contains ``labels``."""
    return sum(v for n, l, v in samples if n == name and labels in l)


def delta(edge_a: dict, edge_b: dict, name: str, labels: str = "") -> float:
    return total(edge_b["prom"], name, labels) - total(edge_a["prom"], name, labels)


def hist_mean(edge_a: dict, edge_b: dict, family: str):
    """Mean of a histogram's observations between two scrapes, or None."""
    n = delta(edge_a, edge_b, family + "_count")
    if n <= 0:
        return None
    return delta(edge_a, edge_b, family + "_sum") / n
