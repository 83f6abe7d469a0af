"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
and the seed into closed-loop client streams of query texts.

A mix file holds only parameters:

- ``streams``: how many closed-loop clients run at once;
- ``queries``: the query names (``queries/<q>.sql`` and ``queries/<q>.py``)
  each stream sends;
Each stream sends its list over and over, stream ``i`` starting at its
``i``-th query, so that two streams of Q1 and Q6 alternate out of step (a
fixed arrival pattern for every seed: an order drawn from the seed
changed how often two Q1s met on the device, and with it the work of a
run). Each stream draws its qgen parameters for each query once per run
(TPC-H's power and throughput tests), so the window measures queries,
not the compiler. Every seed gives every stream the same queries in the
same order; only the drawn literals change. A mix that needs another
policy adds its field here.
"""

from dataclasses import dataclass
from typing import Any, Dict, Iterator, List

import numpy as np


@dataclass(frozen=True)
class Query:
    name: str
    text: str
    params: Dict[str, Any]

    @property
    def key(self) -> str:
        """One key per distinct text: answers are compared per key."""
        return f"{self.name} {sorted(self.params.items())}"


class Stream:
    """An endless closed-loop sequence of queries for one client."""

    def __init__(self, index: int, mix: Dict[str, Any], queries: Dict[str, Any], seed: int):
        self.index = index
        rng = np.random.default_rng([seed % 2**64, index])
        self.queries: List[Query] = []
        for name in mix["queries"]:
            mod, template = queries[name]
            params = mod.draw(rng)
            self.queries.append(Query(name, template.format(**mod.literals(params)), params))

    def __iter__(self) -> Iterator[Query]:
        k = len(self.queries)
        i = self.index
        while True:
            yield self.queries[i % k]
            i += 1


def make_streams(mix: Dict[str, Any], queries: Dict[str, Any], seed: int) -> List[Stream]:
    return [Stream(i, mix, queries, seed) for i in range(int(mix["streams"]))]
