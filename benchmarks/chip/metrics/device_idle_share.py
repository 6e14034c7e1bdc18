"""Share of the traced window in which no operation ran on the device."""


def read(reduced: dict, ctx: dict):
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
