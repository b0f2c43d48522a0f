"""Tokens a row gains a pass: the positions the window's passes unmasked
over its active row-passes, from the difference of the engine's counters
(``stats()["engine"]["tokens_unmasked"]`` / ``["row_passes"]``) across
the window. With a block of 4, two denoising passes a block and the
commit a pass of its own, a block costs three passes: 1.33; a commit that
rode on the next block's first pass would read 2.0. The line beside it
says what a pass reads at the least (``benchmark/flops_sdar.py``: the
experts hit and the positions attended from the program's counters) and
the rate that bound gives at the chip's memory bandwidth. Nothing to
read where the engine counts no passes (a program before PR 46)."""

from benchmark import flops, flops_sdar, harness


def read(summary):
    passes = summary.get("window_passes")
    if not passes or not passes["row_passes"]:
        return None
    value = passes["tokens_unmasked"] / passes["row_passes"]
    cfg, steps = summary["config"], summary["decode_steps"]
    attended = (summary.get("window_positions_by_kind") or {}).get("kv")
    if summary["platform"] != "cpu" and steps and attended:
        moved = flops_sdar.pass_bytes(
            cfg, cfg["num_layers"] * cfg["experts_count"], attended)
        least = moved / flops.peaks(summary["device_kind"])["hbm_bytes_per_s"]
        harness.say(
            f"tokens_per_pass.serve: {passes}; a pass over "
            f"{passes['row_passes'] / steps:.1f} rows reads at least "
            f"{moved:.0f} bytes (every "
            f"expert hit), {1e3 * least:.2f} ms: at most "
            f"{passes['tokens_unmasked'] / steps / least:.0f} tokens/s")
    return value
