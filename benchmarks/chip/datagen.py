"""TPC-H-shaped tables from a seed, with cardinalities fixed by the scale.

Each table is a generator file of its own, ``tables/<table>.py``, found by
name (see ``tables/_common.py``); this file only finds them and writes what
they make.  The generators are dbgen-shaped, not dbgen, and differ from
``benchmarks/datagen.py``, which they were copied from, in what the benchmark
needs:

- **row counts depend on the scale alone.**  ``lines_per_order`` comes from
  a stream fixed in ``tables/_common.py``, as dbgen's row counts are fixed by
  its scale factor; every value comes from ``seed``.  A new seed therefore
  gives the same shapes, so it need not compile anything new.
- they are vectorised (no per-row python), write decimals as the unscaled
  int64 the engine stores (field metadata ``kind=decimal, scale``), and only
  the tables asked for are made, all columns of each.

Data is made anew in every run: a run's set-up does the same work whatever
ran before it in the checkout.
"""
from __future__ import annotations

import importlib
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable

from .tables._common import DEVICE_WIDTH, forget  # noqa: F401 — work.py


def table_module(table: str):
    try:
        return importlib.import_module(f"{__package__}.tables.{table}")
    except ModuleNotFoundError as e:
        raise SystemExit(f"no generator for table {table!r}: add "
                         f"benchmarks/chip/tables/{table}.py ({e})")


def columns(table: str) -> Dict[str, str]:
    """{column: kind} of one table."""
    return table_module(table).COLUMNS


def cardinalities(scale: float, tables: Iterable[str]) -> Dict[str, int]:
    """Rows of each table at ``scale``: a function of the scale alone."""
    return {t: int(table_module(t).rows(scale)) for t in sorted(set(tables))}


def generate_tables(scale: float, seed: int, tables: Iterable[str]):
    """{table: pyarrow.Table}."""
    try:
        return {t: table_module(t).generate(scale, seed)
                for t in sorted(set(tables))}
    finally:
        forget()


def write_data(ddir: str, scale: float, seed: int,
               tables: Iterable[str]) -> str:
    """Make ``<table>.parquet`` for every table asked for in ``ddir``, in
    place of whatever an earlier run left there, and have it on the disk
    before returning, so that no write-back falls into the window."""
    import pyarrow.parquet as pq

    shutil.rmtree(ddir, ignore_errors=True)
    os.makedirs(ddir)

    def write(item):
        name, table = item
        path = os.path.join(ddir, f"{name}.parquet")
        pq.write_table(table, path, compression="zstd",
                       row_group_size=1 << 19)
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    with ThreadPoolExecutor(max_workers=3) as pool:
        list(pool.map(write, generate_tables(scale, seed, tables).items()))
    return ddir
