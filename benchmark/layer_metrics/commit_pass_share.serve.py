"""Share of the window's active row-passes that unmasked nothing (the
commit pass over a finished block, which only leaves the block's final
keys and values in the cache), from the difference of the engine's
counters (``commit_row_passes`` / ``row_passes``) across the window. A
third at a block of 4 and two denoising passes; 0 once a commit rides on
the next block's first pass. Nothing to read where the engine counts no
passes."""


def read(summary):
    passes = summary.get("window_passes")
    if not passes or not passes["row_passes"]:
        return None
    return 100.0 * passes["commit_row_passes"] / passes["row_passes"]
