"""The client-side reader of first-token waits: from each due time, a
miss counted with its whole wait."""
from types import SimpleNamespace

import numpy as np

from perfbench import harness, readers, traffic


def _req(key, due, times):
    r = traffic.Req(key, np.zeros(4, np.int32), 3, due)
    r.times = list(times)
    return r


def _ctx(reqs, window=(10.0, 20.0), closed=30.0, **kw):
    client = SimpleNamespace(all=reqs, step_walls=[])
    return harness.Ctx({}, {}, window, client, kw.get("stats0"),
                       kw.get("stats1"), 0.0, closed)


def test_first_token_waits_run_from_the_due_time():
    reqs = [_req(1, 9.0, [9.5]),            # due before the window
            _req(2, 10.0, [10.25, 10.3]),
            _req(3, 15.0, [18.0]),
            _req(4, 19.5, [])]              # a miss: waits until closed
    got = readers.first_token_ms(_ctx(reqs))
    assert got == [(10.0, 250.0), (15.0, 3000.0), (19.5, 10500.0)]
    assert readers.percentile([w for _, w in got], 95) == 10500.0
