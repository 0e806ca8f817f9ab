"""itl_p95_ms.open: ``itl_p95_ms`` in an open-loop cell. Its slowest
gaps are those of the steps that carry a prefill, and which prompts
meet in one step changes with the order of the arrivals, so it spreads
far more from run to run than in a closed loop and has a bound of its
own."""

from chip import harness

read = harness.reader("itl_p95_ms")
