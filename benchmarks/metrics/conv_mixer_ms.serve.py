"""Device self time of one paged-decode program under the program's ``conv``
and ``state_write`` scopes: the gated short-conv operators of all the model's
conv layers (in-projection, both gates, taps, out-projection, the read of the
slot's state) and the write of their new state into the slot-indexed array
(0.4 us of 0.36 ms on the chip: too small for a metric of its own), median
over the traced ``jit_step`` programs, in milliseconds. The
operator is XLA fusions whose weights the compiler fetches asynchronously under
the ops before them; those fetches' waits carry no scope and are not in this
number (so it has no roofline share: PERF.md section 6, PR 28). Moves
tpot_p50_s."""

from benchmarks.metrics._common import DECODE_MODULE
from benchmarks.metrics._scope_segments import median_ms


def read(run: dict):
    if run["artefacts"]["kind"] != "serve":
        return None
    return median_ms(run, DECODE_MODULE, "conv", "state_write")
