"""Seconds the process spent compiling (trace + lowering + backend, or the
retrieval of a persistent-cache hit), from the program's ``compile_report``
counters (telemetry/compile_events.py). Moves ``setup_s``."""


def read(run: dict):
    totals = run["compile"]
    return float(totals["compile_secs"]) if totals else None
