"""What every oracle shares: loading columns of the generated parquet into
pandas, and turning numbers into the python values an answer holds.

An oracle is the plain reference: the query in pandas over the same files,
independent of the engine.  Decimals stay the unscaled int64 the files store,
so sums are exact.  ``money="float32"`` is the CONTROL, not a reference: the
same query with every decimal column held in float32, the step that would
tempt on a chip with no native 64-bit arithmetic.  The configuration's
guarantee is exact answers, so the control has to come out as not correct.

Each oracle's ``answer(ddir, money)`` returns ``(rows, order_keys, limit)``:
rows in query order as tuples of python values, ``order_keys`` as
``[(column index, ascending)]``.
"""
from __future__ import annotations

import datetime
import os
from decimal import Decimal

EPOCH = datetime.date(1970, 1, 1)
MONEY = ("int64", "float32")


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def date(n) -> datetime.date:
    return EPOCH + datetime.timedelta(days=int(n))


def dec(unscaled, scale: int) -> Decimal:
    """An unscaled sum as the decimal the engine returns.  A float (the
    control's) is rounded to the nearest unscaled integer first."""
    import numpy as np

    if isinstance(unscaled, (float, np.floating)):
        unscaled = round(float(unscaled))
    return Decimal(int(unscaled)).scaleb(-scale)


def load(ddir: str, table: str, columns, money: str = "int64"):
    """Columns of one table as a DataFrame: dates as int days, strings as
    objects (categories where the file holds a dictionary: 60M python
    strings a column take a minute to make), decimals as unscaled int64 (or
    float32 for the control)."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    if money not in MONEY:
        raise ValueError(f"money is one of {MONEY}, not {money!r}")
    t = pq.read_table(os.path.join(ddir, f"{table}.parquet"),
                      columns=list(columns))
    cols = {}
    for field in t.schema:
        col = t.column(field.name)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        if pa.types.is_dictionary(col.type) or pa.types.is_string(col.type):
            cols[field.name] = col.to_pandas()      # categorical, or objects
            continue
        arr = col.to_numpy()
        if (field.metadata or {}).get(b"kind") == b"decimal" \
                and money == "float32":
            arr = arr.astype(np.float32)
        cols[field.name] = arr
    return pd.DataFrame(cols, copy=False)
