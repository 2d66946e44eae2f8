"""decode_table_share.lat (%; layer: kernels,
kernels/decode_attention/paged.py): 100 x the pages the paged decode
kernel streams (the engine's counter ``decode_pages_streamed``: a slot's
live pages, per active (step, slot) pair) over the window, on the base of
the table width it would sweep for the same pairs
(``decode_pages_table``), in a latency cell.  Nothing where no decode step
ran."""


def read(rec):
    table = rec.counters.get("decode_pages_table")
    if not table:
        return None
    return 100.0 * rec.counters["decode_pages_streamed"] / table
