"""Occupied slots per decode step over the slots, from the difference of
``handle.stats()`` across the window."""


def read(summary):
    if not summary.get("decode_steps"):
        return None
    return (100.0 * summary["occupied_slot_steps"]
            / (summary["decode_steps"] * summary["slots"]))
