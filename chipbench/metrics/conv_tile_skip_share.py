"""Share of the conv-pool stages' 128x128 tile pairs that the
event-driven contraction skipped: the ``conv_tiles_skipped`` over the
``conv_tile_pairs`` counts of the ``snn.sync`` spans inside the window.
A program or a network without conv-pool stages records neither count,
and there is nothing to read."""

import program_spans


def read(run):
    got = program_spans.in_window(run)
    if not got:
        return None
    syncs = [s.counts for s in got.get("snn.sync", [])
             if "conv_tile_pairs" in s.counts]
    pairs = sum(c["conv_tile_pairs"] for c in syncs)
    return (sum(c["conv_tiles_skipped"] for c in syncs) / pairs if pairs
            else None)
