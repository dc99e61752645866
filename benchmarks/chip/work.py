"""The least work a query needs, from shapes alone (the yardstick a roofline
share is taken against).

A query has to read every column it references in each base table at least
once, at the width the device holds it (``DEVICE_WIDTH``).  That is the same
whatever implements the query: a later PR that fuses, caches or re-plans
changes the time, not this count.  Bytes bound these queries, not
operations: q1 does about a dozen integer operations per 44 bytes read, far
under the chip's operations-to-bytes ratio, so the bound is the HBM rate.
"""
from __future__ import annotations

from typing import Dict, List

from . import datagen


def row_bytes(table: str, columns: List[str]) -> int:
    kinds = datagen.columns(table)
    return sum(datagen.DEVICE_WIDTH[kinds[c]] for c in columns)


def query_bytes(columns: Dict[str, List[str]],
                cardinalities: Dict[str, int]) -> int:
    """Bytes of the referenced columns of every base table, each read
    once."""
    return sum(cardinalities[t] * row_bytes(t, cols)
               for t, cols in columns.items())
