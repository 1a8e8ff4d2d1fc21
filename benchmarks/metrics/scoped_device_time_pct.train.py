"""Share of the device's busy (self) time in the traced train steps that
carries a name of the program's scope vocabulary. The notes line holds the
whole table: median ms a step by scope x forward / backward / recompute, and
the largest ops no scope covers, by source line. Moves
train_tokens_per_s_per_chip."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import TRAIN_MODULE, say


def read(run: dict):
    table = program_trace.device_table(run)
    if table is None:
        return None
    say(program_trace="scopes", module="jit_step_fn",
        ms_a_step_by_scope_fwd_bwd_remat=program_trace.median_by_scope_ms(table, TRAIN_MODULE),
        unscoped_seconds=program_trace.largest_unscoped(table),
        busy_s=table["busy_s"], with_op_name_s=table["tagged_s"])
    return 100.0 * table["scoped_s"] / table["busy_s"]
