"""Share of the stack kernel's 128x128 tile pairs that the event-driven
contraction skipped: the ``tiles_skipped`` over the ``tile_pairs`` counts
of the ``snn.sync`` spans inside the window (each sync reads back the
previous chunk's telemetry)."""

import program_spans


def read(run):
    got = program_spans.in_window(run)
    if not got:
        return None
    syncs = [s.counts for s in got.get("snn.sync", [])
             if "tile_pairs" in s.counts]
    pairs = sum(c["tile_pairs"] for c in syncs)
    return sum(c["tiles_skipped"] for c in syncs) / pairs if pairs else None
