"""decode_step_ms.open: ``decode_step_ms`` in an open-loop cell, where
the decode step sets the gaps between tokens (``itl_p95_ms.open``) rather
than a closed loop's throughput."""

from chip import harness

read = harness.reader("decode_step_ms")
