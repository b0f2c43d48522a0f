"""Of the positions the window's decode steps attended, the share read
from ring leaves (the window layers' at most ``window`` a row a layer)
and not from ``max_seq``-long key/value leaves (the full layers' whole
contexts): the engine's counter ``decode_positions_by_kind`` across the
window. With ``w`` window layers to ``f`` full ones and contexts of
``c`` positions it is ``w window / (w window + f c)``: 6 x 128 over 6 x
128 + 2 x 4000 is 9%. Nothing to read where the engine counts no such
thing (a model whose layers all keep one kind of cache)."""


def read(summary):
    by_kind = summary.get("window_positions_by_kind")
    if not by_kind or not sum(by_kind.values()):
        return None
    return 100.0 * by_kind.get("ring", 0.0) / sum(by_kind.values())
