"""CPU tests of the ``queue_wait_p95_ms`` reader on hand-made runs.

The reader takes each window arrival's ``t_admitted - t_submit``, with
the window's close standing in for a request still queued there, and
reads nothing from a closed loop or from a program whose handles carry
no ``t_admitted`` stamp.
"""

from __future__ import annotations

import types

import pytest

from chip import harness

read = harness.reader("queue_wait_p95_ms")


def _arrival(submitted, admitted):
    h = types.SimpleNamespace(t_submit=submitted, t_admitted=admitted,
                              t_first_token=None, t_tokens=[], prompt=[0])
    return submitted, submitted, h


def _run(loop, arrivals, t1=12.0):
    return harness.Run(loop=loop, mix={"slots": 4}, dm=None, peak=None,
                       setup_s=5.0, t0=10.0, t1=t1, handles=[],
                       arrivals=arrivals, counters={}, prefilled=[])


# (arrivals as (t_submit, t_admitted), window close, milliseconds)
CASES = {
    # waits .08, .01 and .97 (never admitted: 12 - 11.03); p95 is the
    # largest of three
    "one_queued_at_close": ([(10.12, 10.2), (11.14, 11.15), (11.03, None)],
                            12.0, 970.0),
    # an admission stamped after the close counts up to the close
    "admitted_after_close": ([(11.5, 12.5), (10.0, 10.1)], 12.0, 500.0),
    # twenty waits of 1..20 ms: the 95th percentile is a measured one
    "twenty_waits": ([(10.0, 10.0 + k / 1e3) for k in range(1, 21)],
                     12.0, 19.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_queue_wait_by_hand(case):
    pairs, t1, want = CASES[case]
    run = _run("open", [_arrival(s, a) for s, a in pairs], t1)
    assert read(run) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("why", ["closed_loop", "no_arrivals",
                                 "program_without_stamp"])
def test_queue_wait_reads_nothing(why):
    arrivals = [_arrival(10.1, 10.2)]
    if why == "closed_loop":
        run = _run("closed", arrivals)
    elif why == "no_arrivals":
        run = _run("open", [])
    else:
        del arrivals[0][2].t_admitted
        run = _run("open", arrivals)
    assert read(run) is None
