"""Process start to the opening of the measured window: imports, mesh,
weights from the seed on the device, compile or cache hit, the first
steps (training) or warm-up of every shape (serving). The reference's
time is not in it."""


def read(summary):
    return summary["setup_s"]
