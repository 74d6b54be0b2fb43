"""KV cache: positions the window layers' decode gathers read
(``kv_window_positions_read_total``: the table entries that cover a window,
whatever the row holds) over the positions resident for those rows
(``kv_positions_resident_total``), summed over window layers and decode
ticks (metricsz, after less before). Under 100 where rows are longer than
the window and the read follows the window; a read of every row's whole
virtual context would be the table's reach over the rows' mean length."""
SOURCE = "program_counter"
UNIT = "%"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    read_, resident = (delta.get("kv_window_positions_read_total"),
                       delta.get("kv_positions_resident_total"))
    if read_ is None or not resident:
        return None
    return 100.0 * read_ / resident
