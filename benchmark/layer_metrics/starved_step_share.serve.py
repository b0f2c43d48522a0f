"""Share of the window's steady passes of the serving loop that came to
their first enqueue with nothing left running on the device
(``serve.step`` spans with ``starved=1`` over those that carry the
attribute and did not run under the profiler): the window's own idle
signal. An earlier line gives the same share over the traced slice's
steps, to be read beside ``device_idle_share.serve`` of those seconds,
and the steps one of whose enqueues returned to find the program before
it finished (``dry_enqueues``: a pass that dispatches several prefills
starves the chip between them without ever reading ``starved``).
The ring holds the run's last spans only: that line says how much of the
window they cover."""

from benchmark import harness, stalls


def read(summary):
    if "served_tokens" not in summary:
        return None
    shares = stalls.starved_shares(summary)
    if shares is None:
        return None
    stalls.say_once(summary, harness.say)
    (starved, steps, dry), (sliced, slice_steps, slice_dry) = (
        shares["window"], shares["profiled"])
    harness.say(
        f"starved_step_share.serve: {starved} of {steps} steady steps of "
        f"the window starved at their first enqueue ({dry} had an "
        f"enqueue return to a dry device); {sliced} of {slice_steps} under "
        f"the profiler"
        + (f" ({100.0 * sliced / slice_steps:.3f}%; {slice_dry} with a dry "
           f"enqueue, {100.0 * slice_dry / slice_steps:.3f}%)"
           if slice_steps else ""))
    return 100.0 * starved / steps if steps else None
