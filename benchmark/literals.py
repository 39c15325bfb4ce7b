"""Draws a query's substitution values from its domain file and fills the
text.  One generator reads every ``<q>.params.json``; a new query brings a
text and a domain file and no code."""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
PLACEHOLDER = re.compile(r"\[([A-Z_0-9]+)\]")


class Query:
    def __init__(self, name: str, directory: str = None):
        directory = directory or os.path.join(HERE, "queries")
        with open(os.path.join(directory, name + ".sql")) as f:
            self.template = f.read()
        with open(os.path.join(directory, name + ".params.json")) as f:
            spec = json.load(f)
        self.name = name
        self.params = spec["params"]
        self.distinct_texts = spec.get("distinct_texts")
        missing = set(PLACEHOLDER.findall(self.template)) - set(self.params)
        if missing:
            raise ValueError(f"{name}: no domain for {sorted(missing)}")

    def domain_size(self) -> int:
        size = 1
        for key in sorted(self.params):
            dom = self.params[key]
            size *= len(dom["choice"]) if "choice" in dom \
                else dom["int_range"][1] - dom["int_range"][0] + 1
        return size

    def nth(self, index: int) -> dict:
        """The ``index``-th member of the domain (mixed radix over the
        parameters in name order)."""
        values = {}
        for key in sorted(self.params):
            dom = self.params[key]
            if "choice" in dom:
                index, digit = divmod(index, len(dom["choice"]))
                values[key] = dom["choice"][digit]
            else:
                lo, hi = dom["int_range"]
                index, digit = divmod(index, hi - lo + 1)
                values[key] = lo + digit
        return values

    def fill(self, values: dict) -> str:
        return PLACEHOLDER.sub(lambda m: str(values[m.group(1)]),
                               self.template)

    def words(self) -> set:
        """Every identifier-like word of the text (its columns among them)."""
        return set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", self.template))


class LiteralPool:
    """Substitution values for all the streams of a run, drawn from the seed
    without replacement: each text's domain is walked in a seeded order, and
    shuffled anew when it is used up.  So no text repeats exactly until its
    domain is exhausted, whatever the seed, and every seed does the same
    amount of work in another order."""

    def __init__(self, queries: dict, seed: int):
        import threading

        import numpy as np
        self.queries = queries
        self.rng = np.random.default_rng([seed, 15485863])
        self.lock = threading.Lock()
        self.left = {name: [] for name in queries}

    def draw(self, name: str) -> dict:
        query = self.queries[name]
        with self.lock:
            if not self.left[name]:
                self.left[name] = self.rng.permutation(
                    query.domain_size()).tolist()
            return query.nth(self.left[name].pop())
