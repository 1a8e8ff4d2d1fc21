"""Share of the device's busy (self) time in the traced part of a serving
window that carries a name of the program's scope vocabulary. The notes line
holds the whole table: median ms a program by scope, for the decode and the
chunk-prefill program, and the largest ops no scope covers, by source line.
Moves tpot_p50_s."""

from benchmarks.harness import program_trace
from benchmarks.metrics._common import DECODE_MODULE, PREFILL_MODULE, say


def read(run: dict):
    table = program_trace.device_table(run)
    if table is None:
        return None
    fwd = lambda rows: {scope: cell["fwd"] for scope, cell in rows.items()}
    say(program_trace="scopes",
        ms_a_decode_program_by_scope=fwd(program_trace.median_by_scope_ms(table, DECODE_MODULE)),
        ms_a_chunk_program_by_scope=fwd(program_trace.median_by_scope_ms(table, PREFILL_MODULE)),
        unscoped_seconds=program_trace.largest_unscoped(table),
        busy_s=table["busy_s"], with_op_name_s=table["tagged_s"])
    return 100.0 * table["scoped_s"] / table["busy_s"]
