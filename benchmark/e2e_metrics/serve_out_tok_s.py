"""Generated tokens attributed to the window (each request's tokens
times the share of its submit-to-reply time inside the window, by the
benchmark's clock: ``runners/serve.py`` ``tokens_in_window``), over the
window's seconds."""


def read(summary):
    if "served_tokens" not in summary:
        return None
    return summary["served_tokens"] / summary["window_s"]
