"""A percentile (nearest rank) of one column of the engine's flight recorder
over the iterations that began inside the window and outside the seconds the
profiler took: the ring rows of ``LLMEngine.stats()`` as every poll and the
report after the window saw them (``engine_longest_iter`` reads the same
rows). params {"column": a ring column, "q"}. With ``admitted`` and 0.9 it
says which shape a closed loop's admission settled in: 4 where every
iteration admits 3 or 4 requests, 10 and more where the callers fell into
one cohort that two iterations admit and seven admit none of. None where the
program has no recorder or no such column."""
from benchmarks.harness.rates import percentile
from benchmarks.readers.engine_longest_iter import in_window, ring_rows, row_seconds


def read(ctx, params):
    rows = ring_rows(ctx)
    starts = in_window(ctx, {s: row_seconds(row) for s, row in rows.items()})
    values = [rows[s][params["column"]] for s in starts
              if params["column"] in rows[s]]
    return percentile(values, params["q"]) if values else None
