"""What the table generators share: the kinds a column can have, arrow
builders, the scale's row counts, and the order skeleton that ``orders`` and
``lineitem`` both stand on.

A table is a file of its own here, ``tables/<table>.py``, found by name:
``COLUMNS`` ({column: kind}), ``rows(scale)`` (a function of the scale
alone, as dbgen's row counts are) and ``generate(scale, seed)`` (a
``pyarrow.Table``; every value from ``seed``, by a stream of the table's own,
so a table reads the same whichever others are made beside it).  A later PR
that needs ``part`` or ``supplier`` adds ``tables/part.py`` and edits
nothing.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

EPOCH_1992 = 8035            # days: 1992-01-01
LAST_ORDERDATE = 10440 - 121  # spec: orders end 121 days before 1998-12-01
# the one stream that no seed moves: how many lineitems each order has
CARDINALITY_STREAM = 0x7C9D

# bytes a value of each kind takes on the device: strings are int32
# dictionary codes, dates int32 days, decimals and keys int64
DEVICE_WIDTH = {"int64": 8, "decimal": 8, "int32": 4, "date": 4, "string": 4}

WORDS = [
    "the", "special", "pending", "final", "regular", "express", "furiously",
    "carefully", "quickly", "deposits", "requests", "accounts", "packages",
    "instructions", "theodolites", "dependencies", "foxes", "ideas", "pinto",
    "beans", "slyly", "blithely", "even", "bold", "silent", "unusual",
    "customer", "complaints", "sleep", "wake", "haggle",
]


def counts(scale: float) -> Dict[str, int]:
    """Rows of the tables whose size is the scale times a constant."""
    return {"part": max(1, int(200_000 * scale)),
            "supplier": max(1, int(10_000 * scale)),
            "customer": max(1, int(150_000 * scale)),
            "orders": max(1, int(1_500_000 * scale))}


def stream(seed: int, i: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), i])


def _resume(state: dict) -> np.random.Generator:
    bits = np.random.PCG64()
    bits.state = state
    return np.random.Generator(bits)


def lines_per_order(n_ord: int) -> np.ndarray:
    return np.random.default_rng(CARDINALITY_STREAM).integers(1, 8, n_ord)


@functools.lru_cache(maxsize=1)
def _skeleton(scale: float, seed: int) -> dict:
    c = counts(scale)
    n_ord, n_cust = c["orders"], c["customer"]
    rng = stream(seed, 2)
    o_key = np.arange(1, n_ord + 1, dtype=np.int64) * 4 - 3     # sparse
    c_key = np.arange(1, n_cust + 1, dtype=np.int64)
    cust_pool = c_key[c_key % 3 != 0] if n_cust >= 3 else c_key
    o_cust = cust_pool[rng.integers(0, len(cust_pool), n_ord)]
    o_date = rng.integers(EPOCH_1992, LAST_ORDERDATE, n_ord).astype(np.int32)
    lines = lines_per_order(n_ord)
    rng_l = stream(seed, 3)
    n_li = int(lines.sum())
    l_odate = np.repeat(o_date, lines)
    l_ship = (l_odate + rng_l.integers(1, 122, n_li)).astype(np.int32)
    cutoff = 10471 - 92      # spec: shipped after this is still open
    return {"o_key": o_key, "o_cust": o_cust, "o_date": o_date,
            "lines": lines, "l_odate": l_odate, "l_ship": l_ship,
            "open_line": l_ship > cutoff,
            "orders_stream": rng.bit_generator.state,
            "lineitem_stream": rng_l.bit_generator.state}


def order_skeleton(scale: float, seed: int, table: str):
    """What an order and its lines agree on (keys, customer, order date,
    lines per order, ship dates), and ``table``'s value stream where the
    skeleton left it.  The last one is kept, so that ``orders`` and
    ``lineitem`` made together build it once; ``forget`` frees it."""
    sk = _skeleton(float(scale), int(seed))
    return sk, _resume(sk[f"{table}_stream"])


def forget() -> None:
    _skeleton.cache_clear()


def strings(values, idx):
    import pyarrow as pa

    # dictionary-typed in the file too: the engine reads strings as
    # dictionaries whatever the file says, and the cast is a third of
    # the generator's time
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32)), pa.array(list(values)))


def comments(rng, n: int, lo: int, hi: int):
    """Word-join comments drawn from a pool of at most 64k distinct ones."""
    pool_n = min(n, 1 << 16)
    lengths = rng.integers(lo, hi, pool_n)
    words = rng.integers(0, len(WORDS), (pool_n, hi))
    pool = [" ".join(WORDS[w] for w in words[i, :lengths[i]])
            for i in range(pool_n)]
    return strings(pool, rng.integers(0, pool_n, n))


def tagged(prefix: str, keys: np.ndarray):
    import pyarrow as pa

    return pa.array(np.char.add(prefix, np.char.zfill(
        keys.astype("U9"), 9)).astype(object), type=pa.string())


def dec(cents: np.ndarray, scale: int = 2):
    import pyarrow as pa

    return pa.array(np.asarray(cents, dtype=np.int64)), pa.field(
        "", pa.int64(), nullable=False,
        metadata={b"kind": b"decimal", b"scale": str(scale).encode()})


def date(days):
    import pyarrow as pa

    return pa.array(np.asarray(days, dtype=np.int32)).cast(pa.date32())


def table(cols: Dict[str, object]):
    """{name: array | (array, field)} -> pyarrow.Table, non-nullable."""
    import pyarrow as pa

    fields, arrays = [], []
    for name, col in cols.items():
        arr, field = col if isinstance(col, tuple) else (col, None)
        meta = field.metadata if field is not None else None
        fields.append(pa.field(name, arr.type, nullable=False, metadata=meta))
        arrays.append(arr)
    return pa.table(arrays, schema=pa.schema(fields))
