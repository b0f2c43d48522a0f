"""Tokens of all steps completed in the window, over the window's
seconds (its opening to the last step's completion), per chip."""


def read(summary):
    if "tokens" not in summary:
        return None
    return summary["tokens"] / summary["window_s"] / summary["chips"]
