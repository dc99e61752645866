"""``correct`` has to fail what breaks the configuration's guarantee: the
control (the reference in float32 in the program's place), and a run whose
timed path alters an answer where it is produced.  Small scale, CPU."""
import json

import pyarrow as pa
import pytest

from benchmarks.chip import compare, control, run
from benchmarks.chip.deployments import cluster, standalone

SCALE = "0.02"


@pytest.mark.parametrize("workload", ["sf10_scanagg", "sf1_join",
                                      "sf1_cluster_streams"])
@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_control_in_float32_is_not_correct(workload, seed):
    v = control.control_verdict(workload, seed, scale=float(SCALE))
    assert not v["correct"]
    assert v["numbers"]["answers_wrong"]["value"] >= 1


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _args(workload, seed):
    return ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
            "--trace", "0", "--allow-cpu", "--scale", SCALE]


def test_sound_run_is_correct(capsys):
    assert run.main(_args("sf10_scanagg", 21)) == 0
    out = _last_line(capsys)
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"       # never read as a chip
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"query_s", "setup_s"}


class _Altering:
    """A session whose every ``nth`` answer is altered where it is
    produced."""

    def __init__(self, inner, alter, nth=5):
        self.inner, self.alter, self.nth, self.n = inner, alter, nth, 0

    def sql(self, text):
        self._df = self.inner.sql(text)
        return self

    def to_arrow(self):
        table = self._df.to_arrow()
        self.n += 1
        return self.alter(table) if self.n % self.nth == 0 else table

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _wrap_sessions(monkeypatch, wrap):
    """Every session either deployment hands out goes through ``wrap``."""
    for kind in (standalone, cluster):
        session = kind.Deployment.session
        monkeypatch.setattr(
            kind.Deployment, "session",
            lambda self, session=session: wrap(session(self)))


def _one_digit_off(table):
    """The last column's first value moved by one unit in its last place."""
    col = table.column(table.num_columns - 1)
    values = col.to_pylist()
    step = 1 if isinstance(values[0], int) else \
        type(values[0])(1).scaleb(values[0].as_tuple().exponent)
    values[0] = values[0] + step
    return table.set_column(table.num_columns - 1,
                            table.schema[table.num_columns - 1],
                            pa.array(values, type=col.type))


def _row_dropped(table):
    return table.slice(1)


@pytest.mark.parametrize("workload", ["sf10_scanagg", "sf1_cluster_streams"])
@pytest.mark.parametrize("alter", [_one_digit_off, _row_dropped])
def test_answer_altered_where_it_is_produced_is_not_correct(
        workload, alter, monkeypatch, capsys):
    _wrap_sessions(monkeypatch, lambda ctx: _Altering(ctx, alter))
    assert run.main(_args(workload, 22)) == 0
    out = _last_line(capsys)
    assert not out["correct"]
    assert out["compared"]["answers_wrong"]["value"] >= 1


def test_failed_query_is_missing_and_not_correct(monkeypatch, capsys):
    class Failing(_Altering):
        def to_arrow(self):
            self.n += 1
            if self.n > 4 and self.n % 3 == 0:      # warm-up stays sound
                raise RuntimeError("executor lost")
            return self._df.to_arrow()

    _wrap_sessions(monkeypatch, lambda ctx: Failing(ctx, None))
    assert run.main(_args("sf10_scanagg", 23)) == 0
    out = _last_line(capsys)
    assert not out["correct"] and out["failed"] >= 1
    assert out["compared"]["answers_missing"]["value"] == out["failed"]


def test_compare_holds_order_limit_and_ties():
    oracle = ([(1, 9), (2, 7), (3, 7), (4, 1)], [(1, False)], 2)
    assert compare.compare([(1, 9), (3, 7)], oracle)[0] is None   # a tie
    assert compare.compare([(1, 9), (4, 1)], oracle)[0]           # not one
    assert compare.compare([(2, 7), (1, 9)], oracle)[0]           # order
    assert compare.compare([(1, 9)], oracle)[0]                   # limit
