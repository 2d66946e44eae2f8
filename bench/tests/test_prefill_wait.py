"""``prefill_wait_p90_ms``: the time from a request's slot to its first
token, on hand-made requests and in a traced run of the toy open-loop
cell (tiny.py)."""
import numpy as np
import pytest

from bench import run
from bench.record import Record
from bench.serve import Req, Window
from bench.spec import metric_reader
from bench.tests import tiny

read = metric_reader("prefill_wait_p90_ms")


def _record(requests, t1=100.0):
    window = Window(0.0, t1, requests, [], 0, 4, 16, 0.0, 0)
    return Record(None, None, window, 1.0)


def _req(rid, due, admit_t=None, first_t=None):
    return Req(rid, 0, 0, due, np.zeros(4, np.int32), admit_t=admit_t,
               first_t=first_t)


@pytest.mark.parametrize("requests,expected_ms", [
    # slot to first token, not due to first token
    ([_req(0, 1.0, 2.0, 5.0)], 3000.0),
    # one with no token by the window's end counts with its wait so far
    ([_req(0, 90.0, 96.0)], 4000.0),
    # one never given a slot is the queue's, not the prefill lane's
    ([_req(0, 1.0, 2.0, 3.0), _req(1, 1.0)], 1000.0),
    # given its slot again after its first token: its first admission is
    # not on record
    ([_req(0, 1.0, 2.0, 3.0), _req(1, 1.0, 9.0, 4.0)], 1000.0),
])
def test_reads_slot_to_first_token(requests, expected_ms):
    assert read(_record(requests)) == pytest.approx(expected_ms)


def test_nothing_to_read_is_none():
    assert read(_record([_req(0, 1.0)])) is None
    assert read(_record([])) is None


def test_p90_over_the_admitted():
    reqs = [_req(i, 0.0, 1.0, 1.0 + 0.1 * (i + 1)) for i in range(10)]
    assert read(_record(reqs)) == pytest.approx(
        1e3 * float(np.percentile([0.1 * (i + 1) for i in range(10)], 90)))


def test_traced_open_loop_run_reports_it(tmp_path):
    root = tiny.make_root(tmp_path)
    out = run.run(tiny.args(seed=2**31 + 29, seconds=3.0, trace=1),
                  root=root, chip=tiny.cpu_chip)
    assert out["correct"] is True
    m = out["metrics"]
    assert 0 < m["prefill_wait_p90_ms"]["value"]
    # the wait from a slot to the first token is part of the wait from
    # due to the first token
    assert m["prefill_wait_p90_ms"]["value"] <= 3e3
